"""Mixture-of-Experts layers: a capacity-bounded one over all experts, and a
dropless one over the experts a chip holds.

Capacity-bounded (softmax gate):

GShard-style one-hot dispatch einsums cost O(T^2 k cf d) — quadratic in
tokens — so we use the sort/scatter formulation (as MaxText's dropping MoE
does): flatten (token, slot) pairs, stable-sort by expert, rank within the
expert group via segment starts, scatter into an (E, C, d) buffer, run the
expert FFNs as one batched einsum, and gather back.  Linear dispatch cost;
expert compute is E*C*d*f*3 matmuls with E*C = k*cf*T.

Sharding: tokens are batch-sharded ("data"), experts are sharded over
"model" when divisible (else the FFN dim is); XLA inserts the all-to-alls at
the scatter/gather boundaries.

Dropless (DeepSeek-V3's sigmoid gate, ``dropless_apply``): the layer is
told which experts it holds, ``offset .. offset + E_here``, routes every
token over all experts, sorts the (token, slot) pairs routed to its own by
expert and runs one grouped matmul over the uneven groups
(``jax.lax.ragged_dot``) for gate, up and down, so it drops nothing.
Slots routed to experts held elsewhere are left out: on one chip the
layer's part of the result is what its experts give, plus the shared
experts, which every chip computes alike.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .layers import swiglu, swiglu_spec
from .params import P


def moe_spec(d: int, f: int, n_experts: int) -> Dict:
    return {
        "router": P((d, n_experts), ("d_model", "experts"), scale=0.1),
        "w_gate": P((n_experts, d, f), ("experts", "d_model", "d_ff")),
        "w_up": P((n_experts, d, f), ("experts", "d_model", "d_ff")),
        "w_down": P((n_experts, f, d), ("experts", "d_ff", "d_model")),
    }


def moe_apply(params: Dict, x: jax.Array, *, top_k: int,
              capacity_factor: float = 1.25, constrain=None,
              seq_chunk: int = 512,
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (B, S, d), aux metrics (load-balance & z losses).

    Dispatch is *grouped per batch row* (per sequence): every sort/scatter/
    gather batches over B, so all dispatch buffers shard over the data axis
    — a single global token sort would force multi-hundred-GB replicated
    (B*S*k, d) tensors under SPMD.
    Capacity is per-group, C = ceil(Sc*k*cf/E), the standard per-device
    capacity of GShard-family implementations.

    The sequence is additionally processed in chunks (lax.scan, rematted):
    router logits (B,S,E) and the (B,E,C,d) buffers would otherwise reach
    tens of GB per device for E=384, k=8 at 4k-32k sequence lengths.

    ``constrain`` (optional): sharding constrainer applied to the
    (B, E, C, *) dispatch/expert buffers.
    """
    B, S, d = x.shape
    if S % seq_chunk or S <= seq_chunk:
        return _moe_chunk(params, x, top_k=top_k,
                          capacity_factor=capacity_factor,
                          constrain=constrain)
    n = S // seq_chunk
    xc = x.reshape(B, n, seq_chunk, d).swapaxes(0, 1)

    def body(_, x_chunk):
        out_c, aux_c = _moe_chunk(params, x_chunk, top_k=top_k,
                                  capacity_factor=capacity_factor,
                                  constrain=constrain)
        return 0, (out_c, aux_c)

    _, (out, auxs) = jax.lax.scan(jax.checkpoint(body), 0, xc)
    out = out.swapaxes(0, 1).reshape(B, S, d)
    metrics = jax.tree.map(lambda a: a.mean(), auxs)
    return out, metrics


def _moe_chunk(params: Dict, x: jax.Array, *, top_k: int,
               capacity_factor: float, constrain=None,
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    B, S, d = x.shape
    E = params["router"].shape[-1]
    k = top_k
    Sk = S * k

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)           # (B, S, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- aux losses (Switch/GShard) ---------------------------------------
    me = probs.mean(axis=(0, 1))                               # (E,)
    rows = jnp.arange(B)[:, None]
    counts = jnp.zeros((B, E), jnp.float32).at[
        rows, expert_idx.reshape(B, Sk)].add(1.0)
    ce = counts.sum(axis=0) / (B * Sk)
    aux_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # ---- per-row sort-based dispatch, inverse-mapping form ------------------
    C = max(int(-(-Sk * capacity_factor // E)), 1)
    flat_e = expert_idx.reshape(B, Sk)
    sort_idx = jnp.argsort(flat_e, axis=1, stable=True)        # (B, Sk)
    sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=1)
    tok = (sort_idx // k).astype(jnp.int32)                    # source token
    starts = jnp.cumsum(counts, axis=1) - counts               # (B, E)
    rank = (jnp.arange(Sk)[None, :]
            - jnp.take_along_axis(starts, sorted_e, axis=1)).astype(jnp.int32)
    keep = rank < C
    dest = jnp.where(keep, sorted_e * C + rank, E * C)         # E*C = dropped

    # slot -> source token (inverse mapping): dispatch is ONE gather from x,
    # never materializing the k-times-larger (B, Sk, d) sorted-token tensor.
    src = jnp.zeros((B, E * C), jnp.int32).at[rows, dest].set(tok,
                                                              mode="drop")
    filled = jnp.zeros((B, E * C), bool).at[rows, dest].set(True, mode="drop")
    gate_slot = jnp.zeros((B, E * C), jnp.float32).at[rows, dest].set(
        jnp.take_along_axis(gate_vals.reshape(B, Sk), sort_idx, axis=1),
        mode="drop")

    cst = constrain or (lambda t: t)
    xin = jnp.take_along_axis(x, src[..., None], axis=1)       # (B, EC, d)
    xin = xin * filled[..., None].astype(x.dtype)
    h = cst(xin.reshape(B, E, C, d))

    g = cst(jnp.einsum("becd,edf->becf", h, params["w_gate"]))
    u = cst(jnp.einsum("becd,edf->becf", h, params["w_up"]))
    y = cst(jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u,
                       params["w_down"]))
    yf = y.reshape(B, E * C, d)

    # combine: scatter-add slots back to their source tokens
    updates = yf * (gate_slot[..., None] * filled[..., None]).astype(x.dtype)
    out = jnp.zeros((B, S, d), x.dtype).at[rows, src].add(updates)

    metrics = {
        "moe_aux_loss": aux_loss,
        "moe_z_loss": z_loss,
        "moe_dropped_frac": 1.0 - keep.mean(),
    }
    return out, metrics


def dropless_spec(d: int, f: int, n_experts: int, n_here: int,
                  n_shared: int) -> Dict:
    """Router over all ``n_experts``; the ``n_here`` held experts' weights;
    the shared experts as one SwiGLU of width ``n_shared * f``."""
    return {
        "router": P((d, n_experts), ("d_model", "experts")),
        "w_gate": P((n_here, d, f), ("experts", "d_model", "d_ff")),
        "w_up": P((n_here, d, f), ("experts", "d_model", "d_ff")),
        "w_down": P((n_here, f, d), ("experts", "d_ff", "d_model")),
        "shared": swiglu_spec(d, n_shared * f),
    }


def sigmoid_route(x: jax.Array, router: jax.Array, top_k: int,
                  scale: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """DeepSeek-V3's gate with one group and a zero correction bias:
    ``s = sigmoid(x Wr)`` in float32, the top ``k`` of ``s``, weights
    ``s_sel / sum(s_sel) * scale``.  Returns (s, expert ids, weights)."""
    s = jax.nn.sigmoid(jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                                  router.astype(jnp.float32)))
    w, idx = jax.lax.top_k(s, top_k)
    return s, idx, w / jnp.sum(w, -1, keepdims=True) * scale


def sequence_balance_loss(s: jax.Array, idx: jax.Array) -> jax.Array:
    """DeepSeek-V3's sequence-wise balance loss over all experts, averaged
    over sequences: ``sum_i f_i P_i`` with ``f_i = E/(k S) * #{t: i in
    top-k(t)}`` and ``P_i`` the mean over the sequence of ``s_i`` normed
    over the experts.  s (B,S,E), idx (B,S,k)."""
    B, S, E = s.shape
    k = idx.shape[-1]
    hits = jnp.zeros((B, E), jnp.float32).at[
        jnp.arange(B)[:, None], idx.reshape(B, S * k)].add(1.0)
    f = hits * (E / (k * S))
    p = jnp.mean(s / jnp.sum(s, -1, keepdims=True), axis=1)
    return jnp.mean(jnp.sum(f * p, -1))


def dropless_apply(params: Dict, x: jax.Array, *, top_k: int, offset: int,
                   scale: float) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B,S,d) -> (B,S,d) and the layer's counters: the balance loss, the
    slots routed to the held experts and the largest held expert's slots."""
    B, S, d = x.shape
    T, k = B * S, top_k
    E_here = params["w_gate"].shape[0]
    with jax.named_scope("moe.dispatch"):
        s, idx, w = sigmoid_route(x, params["router"], k, scale)
        local = idx.reshape(T * k) - offset
        held = (local >= 0) & (local < E_here)
        # slots of other chips' experts sort last, past every group
        order = jnp.argsort(jnp.where(held, local, E_here), stable=True)
        sizes = jnp.zeros((E_here,), jnp.int32).at[local].add(
            held.astype(jnp.int32), mode="drop")
        # the chip's kernel leaves rows past the last group unwritten, in
        # its outputs and in the gradients it gives: select them out here
        # and in the combine, so that nothing unwritten reaches x or out
        in_group = jnp.arange(T * k) < jnp.sum(sizes)
        xs = jnp.where(in_group[:, None], x.reshape(T, d)[order // k], 0)
    with jax.named_scope("moe.gmm"):
        g = jax.lax.ragged_dot(xs, params["w_gate"], sizes)
        u = jax.lax.ragged_dot(xs, params["w_up"], sizes)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * u, params["w_down"], sizes)
    with jax.named_scope("moe.combine"):
        slot_row = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        ys = jnp.where(held[:, None], y[slot_row], 0).reshape(T, k, d)
        wt = jnp.where(held.reshape(T, k), w.reshape(T, k), 0.0).astype(x.dtype)
        out = jnp.einsum("tkd,tk->td", ys, wt).reshape(B, S, d)
        out = out + swiglu(params["shared"], x)
    aux = {"moe_balance_loss": sequence_balance_loss(s, idx),
           "moe.slots_here": jnp.sum(sizes).astype(jnp.float32),
           "moe.load_max": jnp.max(sizes).astype(jnp.float32)}
    return out, aux


__all__ = ["moe_spec", "moe_apply", "dropless_spec", "dropless_apply",
           "sigmoid_route", "sequence_balance_loss"]
