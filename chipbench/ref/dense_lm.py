"""Plain float32 reference of the dense decoder LM's train step.

The equations are those the program states for its dense family, written
out here in ``jax.numpy`` with every matmul at ``Precision.HIGHEST``:

* tokens embed through a ``(vocab, d)`` table, which also unembeds (tied);
* each block is pre-norm: ``x += attn(rmsnorm(x))``, ``x += mlp(rmsnorm(x))``
  with RMSNorm (eps from the config, a scale and no bias), rotary position
  embeddings on the whole head (theta from the config, the two halves of a
  head rotated together), causal softmax attention scaled by
  ``head_dim ** -0.5``, and a SwiGLU MLP ``(silu(x Wg) * (x Wu)) Wd``;
* the loss is the mean cross-entropy of each next token over the positions
  the loss mask keeps.

The optimizer is AdamW as configured: global-norm clipping, a warmup and
cosine schedule, weight decay on every leaf, and ``int8_factored`` state:
the first moment stored as int8 with a float32 scale per row of the last
axis, the second factored into row and column means of ``g * g`` for leaves
of two axes or more.  Parameters are stored in bfloat16 between steps, as
the configuration states; all arithmetic is float32.

It runs a block at a time, so that it fits on the chip beside nothing else:
a forward pass keeps each block's input, then a backward pass takes one
block's vector-Jacobian product at a time and keeps only the sums of
squares the global norm needs; a second backward pass, once the norm is
known, recomputes each block's gradients and applies the update to that
block.  The loss head runs over chunks of positions.

Weights come from :func:`init_weights`, the benchmark's own rule, from the
seed alone: the program is handed the same weights, and the reference takes
nothing that the program made.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_KEYS = {"attn": ("wq", "wk", "wv", "wo"),
              "mlp": ("w_gate", "w_up", "w_down")}
NORMS = ("ln1", "ln2")
# a key of one layer's flat dict -> the program's path of its stacked leaf
LEAF_PATH = {**{k: f"blocks/{g}/{k}" for g, ks in BLOCK_KEYS.items()
                for k in ks},
             **{n: f"blocks/{n}/scale" for n in NORMS}}


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float


@dataclasses.dataclass(frozen=True)
class Adam:
    peak_lr: float
    warmup_steps: int
    total_steps: int
    min_lr_ratio: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    clip_norm: float


def identity(x):
    return x


# -- weights -----------------------------------------------------------------

def weight_shapes(s: Shape) -> Dict:
    """The parameter tree as the program lays it out: blocks stacked on a
    leading layer axis."""
    L, d, H, K, Dh, F = (s.n_layers, s.d_model, s.n_heads, s.n_kv_heads,
                         s.head_dim, s.d_ff)
    return {
        "embed": {"embedding": (s.vocab, d)},
        "blocks": {
            "ln1": {"scale": (L, d)},
            "attn": {"wq": (L, d, H, Dh), "wk": (L, d, K, Dh),
                     "wv": (L, d, K, Dh), "wo": (L, H, Dh, d)},
            "ln2": {"scale": (L, d)},
            "mlp": {"w_gate": (L, d, F), "w_up": (L, d, F),
                    "w_down": (L, F, d)},
        },
        "ln_f": {"scale": (d,)},
    }


def _init_std(path: str, s: Shape) -> float:
    fan_in = {"wq": s.d_model, "wk": s.d_model, "wv": s.d_model,
              "wo": s.n_heads * s.head_dim, "w_gate": s.d_model,
              "w_up": s.d_model, "w_down": s.d_ff}
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "embedding":
        return 0.02
    return 1.0 / math.sqrt(fan_in[leaf])


def weight_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(
        int(np.random.default_rng([seed, 0x3E16]).integers(2 ** 31)))


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _init(key, s: Shape, dtype):
    shapes = weight_shapes(s)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, shape), k in zip(flat, keys):
        name = "/".join(p.key for p in path)
        if name.endswith("scale"):
            out.append(jnp.ones(shape, dtype))
        else:
            out.append(jax.random.normal(k, shape, dtype)
                       * jnp.asarray(_init_std(name, s), dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def init_weights(seed: int, s: Shape, dtype=jnp.bfloat16) -> Dict:
    """Every weight from the seed, on the device, in one jitted call:
    norm scales one, the table N(0, 0.02), each matrix N(0, 1/fan_in)."""
    return _init(weight_key(seed), s, jnp.dtype(dtype))


def leaf_paths(tree: Dict) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(p.key for p in path) for path, _ in flat]


# -- the model -----------------------------------------------------------------

def _mm(spec: str, a, b, cast: Callable):
    return jnp.einsum(spec, cast(a), cast(b), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """x (B, S, H, Dh); the first half of each head is rotated against the
    second by angle ``position / theta ** (2i / Dh)``."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(p: Dict, x, s: Shape, cast: Callable = identity):
    """One decoder block.  ``p`` holds one layer's float32 weights."""
    S = x.shape[1]
    pos = jnp.arange(S)
    h = rmsnorm(x, p["ln1"], s.norm_eps)
    q = rope(_mm("bsd,dhk->bshk", h, p["wq"], cast), pos, s.rope_theta)
    k = rope(_mm("bsd,dhk->bshk", h, p["wk"], cast), pos, s.rope_theta)
    v = _mm("bsd,dhk->bshk", h, p["wv"], cast)
    group = s.n_heads // s.n_kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = _mm("bshd,bthd->bhst", q, k, cast) * (s.head_dim ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhst,bthd->bshd", w, v, cast)
    x = x + _mm("bshk,hkd->bsd", o, p["wo"], cast)
    h = rmsnorm(x, p["ln2"], s.norm_eps)
    g = _mm("bsd,df->bsf", h, p["w_gate"], cast)
    u = _mm("bsd,df->bsf", h, p["w_up"], cast)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"], cast)


def head_nll_sum(x, ln_f, table, targets, weights, s: Shape,
                 cast: Callable = identity):
    """Sum of the weighted next-token losses of a chunk of positions."""
    h = rmsnorm(x, ln_f, s.norm_eps)
    logits = _mm("bsd,vd->bsv", h, table, cast)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - ll) * weights)


def layer(params: Dict, i: int) -> Dict:
    """Layer ``i``'s weights, flat: ``wq .. w_down, ln1, ln2``."""
    b = params["blocks"]
    out = {k: b[g][k][i] for g, ks in BLOCK_KEYS.items() for k in ks}
    out.update({n: b[n]["scale"][i] for n in NORMS})
    return out


class Reference:
    """The reference train step, jitted a piece at a time."""

    def __init__(self, s: Shape, adam: Adam, chunk: int = 512,
                 cast: Callable = identity) -> None:
        self.s, self.adam, self.chunk, self.cast = s, adam, chunk, cast
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

        def fwd(p, x):
            return block(f32(p), x, s, cast)

        def bwd(p, x, dy):
            _, vjp = jax.vjp(lambda p_, x_: block(p_, x_, s, cast), f32(p), x)
            return vjp(dy)

        def head(x, ln_f, table, targets, weights, scale):
            fn = lambda x_, n_, t_: head_nll_sum(x_, n_, t_, targets, weights,
                                                 s, cast)
            nll, vjp = jax.vjp(fn, x, ln_f.astype(jnp.float32),
                               table.astype(jnp.float32))
            return (nll,) + vjp(scale)

        self._fwd = jax.jit(fwd)
        self._bwd = jax.jit(bwd)
        self._head = jax.jit(head)
        self._embed = jax.jit(lambda table, tok: table.astype(jnp.float32)[tok])
        self._scatter = jax.jit(
            lambda d_table, tok, dx: d_table.at[tok].add(dx))

    def forward(self, params: Dict, tokens, mask) -> "Forward":
        """The loss, each block's input, and the gradients the head gives:
        of the last block's output, the final norm and the table."""
        s, C = self.s, self.chunk
        B, S = tokens.shape
        if S % C:
            raise ValueError(f"sequence {S} is not a multiple of chunk {C}")
        tokens = jnp.asarray(tokens)
        # position i predicts token i+1, weighted by that token's mask
        targets = jnp.concatenate([tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], 1)
        weights = jnp.concatenate([jnp.asarray(mask, jnp.float32)[:, 1:],
                                   jnp.zeros((B, 1), jnp.float32)], 1)
        denom = jnp.maximum(weights.sum(), 1.0)
        table = params["embed"]["embedding"]
        xs = [self._embed(table, tokens)]
        for i in range(s.n_layers):
            xs.append(self._fwd(layer(params, i), xs[-1]))
        x = xs.pop()
        nll = 0.0
        dx = jnp.zeros_like(x)
        d_lnf = jnp.zeros((s.d_model,), jnp.float32)
        d_table = jnp.zeros(table.shape, jnp.float32)
        for c in range(0, S, C):
            n, dxc, dn, dt = self._head(x[:, c:c + C], params["ln_f"]["scale"],
                                        table, targets[:, c:c + C],
                                        weights[:, c:c + C], 1.0 / denom)
            nll = nll + n
            dx = dx.at[:, c:c + C].set(dxc)
            d_lnf, d_table = d_lnf + dn, d_table + dt
        return Forward(float(nll / denom), tokens, xs, dx, d_lnf, d_table)

    def backward(self, params: Dict, fwd: "Forward",
                 visit: Callable) -> jax.Array:
        """Each block's gradients, last block first, handed to
        ``visit(i, grads)``; returns the gradient of the embedded input."""
        dx = fwd.dx
        for i in reversed(range(self.s.n_layers)):
            dp, dx = self._bwd(layer(params, i), fwd.xs[i], dx)
            visit(i, dp)
        return dx

    def table_grad(self, fwd: "Forward", dx) -> jax.Array:
        """The table's gradient: the head's part and the embedding's."""
        return self._scatter(fwd.d_table, fwd.tokens, dx)

    def loss_and_grads(self, params: Dict, tokens, mask):
        """Loss and gradients of one batch: the block gradients on the host
        (float32 NumPy, one dict per layer), the table and final norm
        gradients on the device."""
        fwd = self.forward(params, tokens, mask)
        grads: List[Dict] = [None] * self.s.n_layers
        dx = self.backward(
            params, fwd, lambda i, dp: grads.__setitem__(i, jax.device_get(dp)))
        return fwd.loss, grads, self.table_grad(fwd, dx), fwd.d_lnf


@dataclasses.dataclass
class Forward:
    loss: float
    tokens: jax.Array
    xs: List[jax.Array]          # each block's input
    dx: jax.Array                # gradient of the last block's output
    d_lnf: jax.Array
    d_table: jax.Array           # the head's part of the table's gradient


@jax.jit
def square_sums(tree):
    return jax.tree.map(lambda g: jnp.sum(jnp.square(g)), tree)


# -- the optimizer ----------------------------------------------------------------

def lr_at(a: Adam, step: int) -> float:
    warm = min(step / max(a.warmup_steps, 1), 1.0)
    frac = min(max((step - a.warmup_steps)
                   / max(a.total_steps - a.warmup_steps, 1), 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * frac))
    return a.peak_lr * warm * (a.min_lr_ratio + (1 - a.min_lr_ratio) * cos)


def quantize(x):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("factored", "a"))
def adam_leaf(p, g, m_q, m_scale, v, step, clip, lr, factored: bool, a: Adam):
    """One leaf's AdamW update; ``v`` is ``(vr, vc)`` where ``factored``.
    Returns the new bfloat16 leaf, int8 first moment with its scale, and
    second moment."""
    t = step.astype(jnp.float32)
    bc1, bc2 = 1.0 - a.b1 ** t, 1.0 - a.b2 ** t
    g = g.astype(jnp.float32) * clip
    m = a.b1 * dequantize(m_q, m_scale) + (1 - a.b1) * g
    if factored:
        g2 = g * g + 1e-30
        vr = a.b2 * v[0] + (1 - a.b2) * g2.mean(axis=-1, keepdims=True)
        vc = a.b2 * v[1] + (1 - a.b2) * g2.mean(axis=-2, keepdims=True)
        v_hat = vr * vc / jnp.maximum(vr.mean(axis=-2, keepdims=True),
                                      1e-30) / bc2
        v_new = (vr, vc)
    else:
        v_new = a.b2 * v + (1 - a.b2) * g * g
        v_hat = v_new / bc2
    p32 = p.astype(jnp.float32)
    delta = (m / bc1) / (jnp.sqrt(v_hat) + a.eps) + a.weight_decay * p32
    q, scale = quantize(m)
    return (p32 - lr * delta).astype(p.dtype), q, scale, v_new


@functools.partial(jax.jit, static_argnames=("a",), donate_argnums=(0, 2, 3))
def adam_row(p, g, m, v, i, step, clip, lr, a: Adam):
    """``adam_leaf`` on row ``i`` of a stacked, factored leaf, written back
    in place: ``m`` is ``(q, scale)`` and ``v`` is ``(vr, vc)``, stacked."""
    row = lambda t: jax.tree.map(lambda x: x[i], t)
    pi, q, scale, (vr, vc) = adam_leaf(p[i], g, m[0][i], m[1][i], row(v),
                                       step, clip, lr, True, a)
    return (p.at[i].set(pi), (m[0].at[i].set(q), m[1].at[i].set(scale)),
            (v[0].at[i].set(vr), v[1].at[i].set(vc)))


class TrainState:
    """The reference's parameters (bfloat16, the program's layout) and its
    optimizer state, stepped by :meth:`step`."""

    def __init__(self, ref: Reference, params: Dict) -> None:
        self.ref, self.params, self.t = ref, params, 0
        self.m = jax.tree.map(
            lambda p: (jnp.zeros(p.shape, jnp.int8),
                       jnp.full(p.shape[:-1] + (1,), 1e-12 / 127.0,
                                jnp.float32)), params)
        self.v = jax.tree.map(
            lambda p: ((jnp.zeros(p.shape[:-1] + (1,), jnp.float32),
                        jnp.zeros(p.shape[:-2] + (1, p.shape[-1]),
                                  jnp.float32))
                       if p.ndim >= 2 else jnp.zeros(p.shape, jnp.float32)),
            params)

    def step(self, tokens, mask) -> Dict:
        """One train step.  Returns its loss and the per-leaf norms of the
        clipped gradient, keyed by the program's leaf path."""
        ref, a, L = self.ref, self.ref.adam, self.ref.s.n_layers
        fwd = ref.forward(self.params, tokens, mask)
        sq = dict.fromkeys(LEAF_PATH.values(), 0.0)

        def add(i, dp):
            for k, v in square_sums(dp).items():
                sq[LEAF_PATH[k]] += float(v)

        d_table = ref.table_grad(fwd, ref.backward(self.params, fwd, add))
        d_lnf = fwd.d_lnf
        sq["embed/embedding"] = float(jnp.sum(d_table * d_table))
        sq["ln_f/scale"] = float(jnp.sum(d_lnf * d_lnf))
        gnorm = math.sqrt(sum(sq.values()))
        clip = min(1.0, a.clip_norm / max(gnorm, 1e-9))
        self.t += 1
        step, lr = jnp.asarray(self.t, jnp.int32), lr_at(a, self.t)
        b = self.params["blocks"]

        def apply(p, g, m, v, factored):
            p, q, scale, v = adam_leaf(p, g, m[0], m[1], v, step, clip, lr,
                                       factored, a)
            return p, (q, scale), v

        # block matrices, a layer at a time as the second backward pass
        # gives their gradients (their moments are per layer), written in
        # place: the layers below, still to come, read only their own rows
        norm_grads = {n: [None] * L for n in NORMS}

        def update(i, dp):
            for g, ks in BLOCK_KEYS.items():
                for k in ks:
                    b[g][k], self.m["blocks"][g][k], self.v["blocks"][g][k] = \
                        adam_row(b[g][k], dp[k], self.m["blocks"][g][k],
                                 self.v["blocks"][g][k], i, step, clip, lr, a)
            for n in NORMS:
                norm_grads[n][i] = dp[n]

        ref.backward(self.params, fwd, update)
        loss = fwd.loss
        del fwd
        # stacked norm scales: their column moment runs over the layers
        for n in NORMS:
            b[n]["scale"], self.m["blocks"][n]["scale"], \
                self.v["blocks"][n]["scale"] = apply(
                    b[n]["scale"], jnp.stack(norm_grads[n]),
                    self.m["blocks"][n]["scale"],
                    self.v["blocks"][n]["scale"], True)
        e = self.params["embed"]
        e["embedding"], self.m["embed"]["embedding"], \
            self.v["embed"]["embedding"] = apply(
                e["embedding"], d_table, self.m["embed"]["embedding"],
                self.v["embed"]["embedding"], True)
        f = self.params["ln_f"]
        f["scale"], self.m["ln_f"]["scale"], self.v["ln_f"]["scale"] = apply(
            f["scale"], d_lnf, self.m["ln_f"]["scale"],
            self.v["ln_f"]["scale"], False)
        return {"loss": loss,
                "grad_norms": {k: clip * math.sqrt(v) for k, v in sq.items()}}
