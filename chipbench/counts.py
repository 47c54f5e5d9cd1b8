"""Operations and bytes that the algorithms need, computed from shapes.

These are the benchmark's own counts: a kernel's roofline share and a
step's model FLOP utilization are read against them, whatever the program
happens to compute on the way (one-hot matmuls, rematerialized layers).
"""

from __future__ import annotations


def crop_bytes(batch: int, out_h: int, out_w: int, channels: int,
               in_itemsize: int = 1, out_itemsize: int = 4) -> int:
    """HBM bytes of one crop/mirror/normalize call: the crop window of each
    image read once, the normalized CHW output written once."""
    pixels = batch * out_h * out_w * channels
    return pixels * (in_itemsize + out_itemsize)


def crop_flops(batch: int, out_h: int, out_w: int, channels: int) -> int:
    """A subtract and a multiply per output element."""
    return 2 * batch * out_h * out_w * channels


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes_per_s: float) -> float:
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the time taken, in percent."""
    if seconds <= 0:
        raise ValueError(f"no time to divide by: {seconds}")
    least = max(flops / peak_flops, nbytes / peak_bytes_per_s)
    return 100.0 * least / seconds


def dense_lm_params(n_layers: int, d_model: int, n_heads: int,
                    n_kv_heads: int, head_dim: int, d_ff: int) -> int:
    """Matmul parameters of the decoder blocks (norm scales left out):
    q, k, v and output projections, and the three SwiGLU matrices."""
    attn = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    mlp = 3 * d_model * d_ff
    return n_layers * (attn + mlp)


def dense_lm_train_flops_per_token(n_layers: int, d_model: int, n_heads: int,
                                   n_kv_heads: int, head_dim: int, d_ff: int,
                                   vocab: int, seq_len: int) -> int:
    """Model FLOPs per trained token, forward and backward: 6 per matmul
    parameter of the blocks and of the unembedding, plus 12 * L * d * S for
    the attention scores and their weighted sum (the PaLM convention, the
    causal mask not subtracted).  Recomputation is not counted."""
    n = dense_lm_params(n_layers, d_model, n_heads, n_kv_heads, head_dim, d_ff)
    n += d_model * vocab
    return 6 * n + 12 * n_layers * d_model * seq_len
