"""Fixtures of the benchmark's CPU tests; the small cells are in
``benchcells``."""

from __future__ import annotations

import pytest

from benchcells import small_image_cell, small_train_cell


@pytest.fixture
def image_cell():
    return small_image_cell()


@pytest.fixture
def train_cell():
    return small_train_cell()
