"""Plain float32 reference of the train step of a DeepSeek-V3-style decoder
(Moonlight-16B-A3B): latent attention, one leading dense layer, then layers
of routed experts beside shared ones, cut to one chip's share of the
experts and a slice of the vocabulary.

Every matmul is ``jnp.einsum`` at ``Precision.HIGHEST`` with float32
results, inside ``jax.default_matmul_precision("highest")``.  Per layer,
pre-norm (RMSNorm, eps from the config, a scale and no bias):
``x += attn(rmsnorm(x))``, ``x += ffn(rmsnorm(x))``.

Attention (MLA, no query compression), per head h:

* ``q = x Wq -> [q_nope (128), q_rope (64)]``;
* ``[c_kv (512), k_rope (64)] = x Wkv_a``, ``c_kv = RMSNorm(c_kv)`` (eps
  1e-6, the published code's default for this norm);
* ``[k_nope (128), v (128)] = c_kv Wkv_b``;
* ``k = [k_nope, rope(k_rope)]``, the rotary part shared by every head,
  ``q = [q_nope, rope(q_rope)]``; causal softmax of ``q k / sqrt(192)``,
  weighted sum of ``v``, and ``o Wo``.

The feed-forward part is a SwiGLU ``(silu(x Wg) * (x Wu)) Wd`` of width
``d_ff`` in the leading dense layers.  In the expert layers:

* routing: ``s = sigmoid(x Wr)`` over all E experts; the top k by
  ``s + b``, where the correction bias ``b`` is zero and not trained; the
  weights ``s_sel / sum(s_sel) * routed_scale``;
* output: ``sum over the selected experts that are held here of
  w_e SwiGLU_e(x)`` plus the shared SwiGLU of width ``n_shared * f``.
  Slots routed to experts held on other chips are left out, as the program
  leaves them out.  Each held expert is computed on every token and
  weighted by zero where it was not selected;
* the sequence-wise balance loss ``sum_i f_i P_i``, with ``f_i = E / (k S)
  * #{t: i selected at t}`` and ``P_i`` the sequence's mean of ``s_i /
  sum_j s_j``, averaged over sequences.

The loss is the mean cross-entropy of each next token over the positions
the loss mask keeps, over the vocabulary slice, plus ``balance_coef`` times
the sum of the expert layers' balance losses.  The head is untied.

Departures from the published model: the rotary pairs dims ``(i, i +
rope/2)``, where the published code pairs interleaved dims; with weights
drawn at random the two differ by a fixed permutation of the rotary columns
of ``Wq`` and ``Wkv_a``.  The correction bias is zero and is not updated.
No multi-token prediction head (``num_nextn_predict_layers`` is 0).

The optimizer is the AdamW of ``ref/dense_lm.py`` (``int8_factored``
state).  An expert leaf is stacked ``(layers, experts here, d, f)``; its
second moment is factored over its last two axes, per layer and expert, as
the program factors it.  Stacked leaves of one axis per layer (the norm
scales) are updated as one ``(layers, n)`` leaf.

It runs a block at a time: a forward pass keeps each block's input, a
backward pass takes each block's vector-Jacobian product and keeps its
gradients on the device, then the update.  Attention runs over chunks of
queries and the feed-forward part over chunks of tokens, each
rematerialized, so the backward pass holds one chunk's scores or
activations at a time.  The loss head runs over chunks of positions.

``capacity_factor`` set above zero makes the control: the capacity-bounded
layer of ``models/moe.py`` (per sequence, in chunks of 512 positions, each
expert keeping the first ``ceil(512 k cf / E)`` slots routed to it) in
place of the dropless one.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from chipbench.ref.dense_lm import (Adam, adam_leaf, adam_row, identity,
                                    lr_at, rmsnorm, rope, square_sums,
                                    weight_key)

HIGHEST = jax.lax.Precision.HIGHEST
LATENT_NORM_EPS = 1e-6
MOE_CHUNK = 512            # positions per capacity group in models/moe.py


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int           # all layers, the dense ones included
    n_dense: int
    d_model: int
    n_heads: int
    kv_rank: int
    nope: int
    rope_dim: int
    v_dim: int
    d_ff: int               # the dense layers' width
    d_expert: int
    n_experts: int          # the router's outputs
    n_here: int
    offset: int
    top_k: int
    n_shared: int
    routed_scale: float
    vocab: int
    rope_theta: float
    norm_eps: float
    balance_coef: float
    capacity_factor: float = 0.0     # > 0: the capacity-bounded control


# -- weights -----------------------------------------------------------------

def _block_shapes(s: Shape, L: int, moe: bool) -> Dict:
    d, H = s.d_model, s.n_heads
    out = {
        "ln1": {"scale": (L, d)},
        "attn": {"wq": (L, d, H, s.nope + s.rope_dim),
                 "wkv_a": (L, d, s.kv_rank + s.rope_dim),
                 "kv_norm": (L, s.kv_rank),
                 "wkv_b": (L, s.kv_rank, H, s.nope + s.v_dim),
                 "wo": (L, H, s.v_dim, d)},
        "ln2": {"scale": (L, d)},
    }
    if moe:
        f, fs, E = s.d_expert, s.n_shared * s.d_expert, s.n_here
        out["moe"] = {"router": (L, d, s.n_experts),
                      "w_gate": (L, E, d, f), "w_up": (L, E, d, f),
                      "w_down": (L, E, f, d),
                      "shared": {"w_gate": (L, d, fs), "w_up": (L, d, fs),
                                 "w_down": (L, fs, d)}}
    else:
        out["mlp"] = {"w_gate": (L, d, s.d_ff), "w_up": (L, d, s.d_ff),
                      "w_down": (L, s.d_ff, d)}
    return out


def weight_shapes(s: Shape) -> Dict:
    """The parameter tree as the program lays it out: each kind of layer
    stacked on a leading layer axis."""
    return {
        "embed": {"embedding": (s.vocab, s.d_model)},
        "dense_blocks": _block_shapes(s, s.n_dense, False),
        "blocks": _block_shapes(s, s.n_layers - s.n_dense, True),
        "ln_f": {"scale": (s.d_model,)},
        "head": {"w_out": (s.d_model, s.vocab)},
    }


def _fan_in(path: str, s: Shape) -> int:
    parts = path.split("/")
    leaf, parent = parts[-1], parts[-2]
    if leaf == "w_down":
        return {"mlp": s.d_ff, "moe": s.d_expert,
                "shared": s.n_shared * s.d_expert}[parent]
    if leaf == "wkv_b":
        return s.kv_rank
    if leaf == "wo":
        return s.n_heads * s.v_dim
    return s.d_model       # wq, wkv_a, router, w_gate, w_up, w_out


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _init(key, s: Shape, dtype):
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(s), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, shape), k in zip(flat, keys):
        name = "/".join(p.key for p in path)
        if name.endswith(("scale", "kv_norm")):
            out.append(jnp.ones(shape, dtype))
        else:
            std = 0.02 if name.endswith("embedding") \
                else 1.0 / math.sqrt(_fan_in(name, s))
            out.append(jax.random.normal(k, shape, dtype)
                       * jnp.asarray(std, dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def init_weights(seed: int, s: Shape, dtype=jnp.bfloat16) -> Dict:
    """Every weight from the seed, on the device, in one jitted call: norm
    scales one, the table N(0, 0.02), each matrix N(0, 1/fan_in)."""
    return _init(weight_key(seed), s, jnp.dtype(dtype))


def flatten(tree: Dict) -> Dict[str, jax.Array]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(p.key for p in path): v for path, v in flat}


def nest(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        *parents, leaf = path.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


# -- the model -----------------------------------------------------------------

def _mm(spec: str, a, b, cast: Callable):
    return jnp.einsum(spec, cast(a), cast(b), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def attention(p: Dict, h, s: Shape, cast: Callable, q_chunk: int):
    B, S, _ = h.shape
    H, nope, r = s.n_heads, s.nope, s.rope_dim
    pos = jnp.arange(S)
    q = _mm("bsd,dhk->bshk", h, p["wq"], cast)
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], pos, s.rope_theta)], -1)
    kva = _mm("bsd,dr->bsr", h, p["wkv_a"], cast)
    c = rmsnorm(kva[..., :s.kv_rank], p["kv_norm"], LATENT_NORM_EPS)
    k_rope = rope(kva[..., None, s.kv_rank:], pos, s.rope_theta)
    kv = _mm("bsr,rhk->bshk", c, p["wkv_b"], cast)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, S, H, r))], -1)
    v = kv[..., nope:]
    scale = (nope + r) ** -0.5
    C = min(q_chunk, S)

    def chunk(args):
        qc, start = args
        scores = _mm("bqhd,bthd->bhqt", qc, k, cast) * scale
        causal = (start + jnp.arange(C))[:, None] >= jnp.arange(S)[None, :]
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _mm("bhqt,bthd->bqhd", w, v, cast)

    qs = q.reshape(B, S // C, C, H, nope + r).swapaxes(0, 1)
    o = jax.lax.map(jax.checkpoint(chunk), (qs, jnp.arange(S // C) * C))
    o = o.swapaxes(0, 1).reshape(B, S, H, s.v_dim)
    return _mm("bshk,hkd->bsd", o, p["wo"], cast)


def swiglu(p: Dict, h, cast: Callable):
    g = _mm("...d,df->...f", h, p["w_gate"], cast)
    u = _mm("...d,df->...f", h, p["w_up"], cast)
    return _mm("...f,fd->...d", jax.nn.silu(g) * u, p["w_down"], cast)


def route(p: Dict, h, s: Shape, cast: Callable):
    """Sigmoid scores (B,S,E), the selected experts (B,S,k) and their
    weights (B,S,k)."""
    scores = jax.nn.sigmoid(_mm("bsd,de->bse", h, p["router"], cast))
    top, idx = jax.lax.top_k(scores, s.top_k)
    return scores, idx, top / jnp.sum(top, -1, keepdims=True) * s.routed_scale


def balance_loss(scores, idx, s: Shape):
    B, S, E = scores.shape
    hits = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(1, 2))
    f = hits * (E / (s.top_k * S))
    prob = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    return jnp.mean(jnp.sum(f * prob, -1))


def kept(hit, s: Shape):
    """The capacity control's mask over one expert's hits (B,S): the first
    ``ceil(512 k cf / E)`` of each chunk of 512 positions."""
    B, S = hit.shape
    C = min(MOE_CHUNK, S)
    cap = max(math.ceil(C * s.top_k * s.capacity_factor / s.n_experts), 1)
    rank = jnp.cumsum(hit.reshape(B, S // C, C), axis=-1) - 1
    return (rank < cap).reshape(B, S)


def by_tokens(fn, chunk: int, *xs):
    """``fn`` of token-wise arrays (B,S,...) over chunks of ``chunk``
    tokens, each rematerialized in the backward pass."""
    B, S = xs[0].shape[:2]
    n = B * S // min(chunk, B * S)
    flat = [x.reshape((n, -1) + x.shape[2:]) for x in xs]
    y = jax.lax.map(jax.checkpoint(lambda a: fn(*a)), flat)
    return y.reshape((B, S) + y.shape[2:])


def experts(p: Dict, h, s: Shape, cast: Callable, chunk: int):
    """The held experts' part of the layer plus the shared experts, and the
    layer's balance loss."""
    scores, idx, w = route(p, h, s, cast)
    held = []
    for e in range(s.n_here):
        hit = idx == s.offset + e
        w_e = jnp.sum(jnp.where(hit, w, 0.0), -1)
        if s.capacity_factor > 0:
            w_e = jnp.where(kept(jnp.any(hit, -1), s), w_e, 0.0)
        held.append(w_e)

    def ffn(hc, wc):
        out = swiglu(p["shared"], hc, cast)
        for e in range(s.n_here):
            out = out + wc[..., e:e + 1] * swiglu(
                {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}, hc, cast)
        return out

    y = by_tokens(ffn, chunk, h, jnp.stack(held, -1))
    return y, balance_loss(scores, idx, s)


def block(p: Dict, x, s: Shape, cast: Callable = identity,
          q_chunk: int = 256, token_chunk: int = 2048):
    """One layer; ``p`` holds its float32 weights.  Returns the output and
    the layer's balance loss (zero for a dense layer)."""
    h = rmsnorm(x, p["ln1"]["scale"], s.norm_eps)
    x = x + attention(p["attn"], h, s, cast, q_chunk)
    h = rmsnorm(x, p["ln2"]["scale"], s.norm_eps)
    if "moe" in p:
        y, bal = experts(p["moe"], h, s, cast, token_chunk)
    else:
        y = by_tokens(lambda hc: swiglu(p["mlp"], hc, cast), token_chunk, h)
        bal = jnp.zeros((), jnp.float32)
    return x + y, bal


def head_nll_sum(x, ln_f, w_out, targets, weights, s: Shape,
                 cast: Callable = identity):
    """Sum of the weighted next-token losses of a chunk of positions."""
    h = rmsnorm(x, ln_f, s.norm_eps)
    logits = _mm("bsd,dv->bsv", h, w_out, cast)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - ll) * weights)


GROUPS = ("dense_blocks", "blocks")


def layers(s: Shape) -> List[tuple]:
    """(group, index in the group) of each layer, bottom first."""
    return ([("dense_blocks", i) for i in range(s.n_dense)]
            + [("blocks", i) for i in range(s.n_layers - s.n_dense)])


def layer(flat: Dict[str, jax.Array], group: str, i: int) -> Dict:
    n = len(group) + 1
    return nest({k[n:]: v[i] for k, v in flat.items()
                 if k.startswith(group + "/")})


@dataclasses.dataclass
class Forward:
    loss: float
    tokens: jax.Array
    xs: List[jax.Array]          # each layer's input
    dx: jax.Array                # gradient of the last layer's output
    d_lnf: jax.Array
    d_head: jax.Array


class Reference:
    """The reference train step, jitted a piece at a time."""

    def __init__(self, s: Shape, adam: Adam, chunk: int = 512,
                 cast: Callable = identity) -> None:
        self.s, self.adam, self.chunk, self.cast = s, adam, chunk, cast
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

        def fwd(p, x):
            return block(f32(p), x, s, cast)

        def bwd(p, x, dy):
            _, vjp = jax.vjp(lambda p_, x_: block(p_, x_, s, cast),
                             f32(p), x)
            return vjp((dy, jnp.asarray(s.balance_coef, jnp.float32)))

        def head(x, ln_f, w_out, targets, weights, scale):
            fn = lambda x_, n_, w_: head_nll_sum(x_, n_, w_, targets,
                                                 weights, s, cast)
            nll, vjp = jax.vjp(fn, x, ln_f.astype(jnp.float32),
                               w_out.astype(jnp.float32))
            return (nll,) + vjp(scale)

        self._fwd = jax.jit(fwd)
        self._bwd = jax.jit(bwd)
        self._head = jax.jit(head)
        self._embed = jax.jit(lambda table, tok: table.astype(jnp.float32)[tok])
        self._table_grad = jax.jit(
            lambda tok, dx, V: jnp.zeros((V, dx.shape[-1]),
                                         jnp.float32).at[tok].add(dx),
            static_argnums=2)

    def forward(self, flat: Dict, tokens, mask) -> Forward:
        s, C = self.s, self.chunk
        B, S = tokens.shape
        if S % C:
            raise ValueError(f"sequence {S} is not a multiple of chunk {C}")
        tokens = jnp.asarray(tokens)
        targets = jnp.concatenate([tokens[:, 1:],
                                   jnp.zeros((B, 1), tokens.dtype)], 1)
        weights = jnp.concatenate([jnp.asarray(mask, jnp.float32)[:, 1:],
                                   jnp.zeros((B, 1), jnp.float32)], 1)
        denom = jnp.maximum(weights.sum(), 1.0)
        xs = [self._embed(flat["embed/embedding"], tokens)]
        bal = 0.0
        for g, i in layers(s):
            x, b = self._fwd(layer(flat, g, i), xs[-1])
            xs.append(x)
            bal = bal + b
        x = xs.pop()
        nll = 0.0
        dx = jnp.zeros_like(x)
        d_lnf = jnp.zeros((s.d_model,), jnp.float32)
        d_head = jnp.zeros((s.d_model, s.vocab), jnp.float32)
        for c in range(0, S, C):
            n, dxc, dn, dh = self._head(x[:, c:c + C], flat["ln_f/scale"],
                                        flat["head/w_out"],
                                        targets[:, c:c + C],
                                        weights[:, c:c + C], 1.0 / denom)
            nll = nll + n
            dx = dx.at[:, c:c + C].set(dxc)
            d_lnf, d_head = d_lnf + dn, d_head + dh
        loss = float(nll / denom) + s.balance_coef * float(bal)
        return Forward(loss, tokens, xs, dx, d_lnf, d_head)

    def grads(self, flat: Dict, tokens, mask):
        """The loss and every leaf's gradient, keyed by the program's leaf
        path; a stacked leaf's gradient is a list, one per layer."""
        fwd = self.forward(flat, tokens, mask)
        out: Dict = {}
        dx = fwd.dx
        for (g, i), x in reversed(list(zip(layers(self.s), fwd.xs))):
            dp, dx = self._bwd(layer(flat, g, i), x, dx)
            for k, v in flatten(dp).items():
                out.setdefault(f"{g}/{k}", [None] * self._count(g))[i] = v
        out["embed/embedding"] = self._table_grad(fwd.tokens, dx,
                                                  self.s.vocab)
        out["ln_f/scale"] = fwd.d_lnf
        out["head/w_out"] = fwd.d_head
        return fwd.loss, out

    def _count(self, group: str) -> int:
        return (self.s.n_dense if group == "dense_blocks"
                else self.s.n_layers - self.s.n_dense)


class TrainState:
    """The reference's parameters (bfloat16, the program's layout, kept flat
    by leaf path) and its optimizer state, stepped by :meth:`step`."""

    def __init__(self, ref: Reference, params: Dict) -> None:
        self.ref, self.t = ref, 0
        self.flat = flatten(params)
        self.m = {k: (jnp.zeros(p.shape, jnp.int8),
                      jnp.full(p.shape[:-1] + (1,), 1e-12 / 127.0,
                               jnp.float32))
                  for k, p in self.flat.items()}
        self.v = {k: ((jnp.zeros(p.shape[:-1] + (1,), jnp.float32),
                       jnp.zeros(p.shape[:-2] + (1, p.shape[-1]),
                                 jnp.float32))
                      if p.ndim >= 2 else jnp.zeros(p.shape, jnp.float32))
                  for k, p in self.flat.items()}

    @property
    def params(self) -> Dict:
        return nest(self.flat)

    def step(self, tokens, mask) -> Dict:
        """One train step.  Returns its loss and the per-leaf norms of the
        clipped gradient, keyed by the program's leaf path."""
        a = self.ref.adam
        with jax.default_matmul_precision("highest"):
            loss, grads = self.ref.grads(self.flat, tokens, mask)
        sq = {k: sum(float(x) for x in square_sums(g if isinstance(g, list)
                                                   else [g]))
              for k, g in grads.items()}
        gnorm = math.sqrt(sum(sq.values()))
        clip = min(1.0, a.clip_norm / max(gnorm, 1e-9))
        self.t += 1
        step, lr = jnp.asarray(self.t, jnp.int32), lr_at(a, self.t)
        for k, g in grads.items():
            p, m, v = self.flat[k], self.m[k], self.v[k]
            if isinstance(g, list) and p.ndim > 2:
                # a layer at a time, as the program's moments are per layer
                for i, gi in enumerate(g):
                    p, m, v = adam_row(p, gi, m, v, i, step, clip, lr, a)
            else:
                # a leaf of its own, or one axis a layer stacked (the norm
                # scales), whose column moment runs over the layers
                g = jnp.stack(g) if isinstance(g, list) else g
                p, q, scale, v = adam_leaf(p, g, m[0], m[1], v, step, clip,
                                           lr, p.ndim >= 2, a)
                m = (q, scale)
            self.flat[k], self.m[k], self.v[k] = p, m, v
        return {"loss": loss,
                "grad_norms": {k: clip * math.sqrt(v) for k, v in sq.items()}}


__all__ = ["Shape", "Reference", "TrainState", "init_weights",
           "weight_shapes", "block", "flatten", "nest", "layers"]
