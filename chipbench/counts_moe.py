"""Operations and bytes of the MLA + routed-expert decoder (DeepSeek-V3
layout) at one chip's share of its experts, computed from shapes; the
counts of ``counts.py`` for this family."""

from __future__ import annotations


def mla_params(d: int, H: int, kv_rank: int, nope: int, rope: int,
               v_dim: int) -> int:
    """Matmul parameters of one latent attention: Wq, Wkv_a, Wkv_b, Wo."""
    return (d * H * (nope + rope) + d * (kv_rank + rope)
            + kv_rank * H * (nope + v_dim) + H * v_dim * d)


def active_params_per_token(n_layers: int, n_dense: int, d: int, H: int,
                            kv_rank: int, nope: int, rope: int, v_dim: int,
                            d_ff: int, d_expert: int, n_experts: int,
                            n_here: int, top_k: int, n_shared: int,
                            vocab: int) -> float:
    """Matmul parameters a token passes through, on this chip: every
    layer's attention, the dense layers' SwiGLU, each expert layer's router
    and shared SwiGLU, the held experts at their expectation ``top_k *
    n_here / n_experts`` of one expert, and the head over the vocabulary
    slice.  The embedding lookup is no matmul."""
    expert = 3 * d * d_expert
    moe_layer = (d * n_experts + n_shared * expert
                 + top_k * n_here / n_experts * expert)
    return (n_layers * mla_params(d, H, kv_rank, nope, rope, v_dim)
            + n_dense * 3 * d * d_ff + (n_layers - n_dense) * moe_layer
            + d * vocab)


def train_flops_per_token(n_layers: int, n_dense: int, d: int, H: int,
                          kv_rank: int, nope: int, rope: int, v_dim: int,
                          d_ff: int, d_expert: int, n_experts: int,
                          n_here: int, top_k: int, n_shared: int, vocab: int,
                          seq_len: int) -> float:
    """Model FLOPs per trained token, forward and backward: 6 per active
    matmul parameter, plus ``6 * L * S * H * (nope + rope + v)`` for the
    scores and the weighted sum (no causal discount).  Recomputation is not
    counted."""
    n = active_params_per_token(n_layers, n_dense, d, H, kv_rank, nope, rope,
                                v_dim, d_ff, d_expert, n_experts, n_here,
                                top_k, n_shared, vocab)
    return 6 * n + 6 * n_layers * seq_len * H * (nope + rope + v_dim)


def expert_gmm_flops(slots: float, d: int, f: int) -> float:
    """The grouped matmuls of ``slots`` routed slots, forward and backward:
    three matmuls of ``2 d f`` a slot forward, twice that backward."""
    return 18 * slots * d * f


def expert_gmm_bytes(slots: float, n_layers: int, n_here: int, d: int,
                     f: int, itemsize: int = 2) -> float:
    """HBM bytes of those grouped matmuls in ``n_layers`` expert layers,
    forward and backward: each layer's held experts' three matrices read
    by each of its nine matmuls (a weight gradient written in place of a
    read), and each matmul's activations read and written once: in the
    forward ``d`` in and ``f`` out (gate, up), ``f`` in and ``d`` out
    (down), in the backward each of those twice."""
    weights = 9 * n_layers * n_here * d * f * itemsize
    acts = 9 * slots * (d + f) * itemsize
    return weights + acts

