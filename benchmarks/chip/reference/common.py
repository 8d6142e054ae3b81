"""Plain float32 pieces of a decoder or encoder transformer, and AdamW.

Written from the published equations, independent of ``src/repro``: no
import from the program, no weights, scales or tables taken from it.  Every
matrix product goes through a :class:`Math`, which computes it in float32 at
``HIGHEST`` precision (a TPU otherwise rounds float32 operands to bfloat16),
or, for the control, with both operands rounded to scaled float8 (e4m3) --
the step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _fp8(x):
    """x rounded to float8 e4m3 under one scale for the whole tensor, as
    scaled fp8 training does, and returned in float32.  The scale takes no
    gradient and the rounding passes it straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


@dataclasses.dataclass(frozen=True)
class Math:
    """How matrix products are computed: ``"f32"`` or ``"fp8"``."""
    kind: str = "f32"

    def ein(self, spec: str, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.kind == "fp8":
            a, b = _fp8(a), _fp8(b)
        elif self.kind != "f32":
            raise ValueError(f"unknown precision {self.kind!r}")
        return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotary embedding over the sequence axis 1 of x [B, S, H, D], in the
    split-half layout: (x1, x2) -> (x1 cos - x2 sin, x1 sin + x2 cos)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(m: Math, p, x, *, heads, kv_heads, head_dim, theta, causal,
              q_block=512):
    """Grouped-query self-attention with RoPE, softmax(q k^T / sqrt(D)) v,
    one block of ``q_block`` queries at a time (recomputed in the backward
    pass) so that the [S, S] scores of all heads never coexist."""
    B, S, _ = x.shape
    G = heads // kv_heads
    q = rope(m.ein("bsd,dhk->bshk", x, p["wq"]), theta)
    k = rope(m.ein("bsd,dhk->bshk", x, p["wk"]), theta)
    v = m.ein("bsd,dhk->bshk", x, p["wv"])
    qb = min(q_block, S)
    q = q.reshape(B, S // qb, qb, kv_heads, G, head_dim)

    @jax.checkpoint
    def block(args):
        qc, i = args                                   # [B, qb, KV, G, D]
        s = m.ein("bckgd,btkd->bkgct", qc, k) / jnp.sqrt(float(head_dim))
        if causal:
            qpos = i * qb + jnp.arange(qb)
            s = jnp.where(qpos[:, None] >= jnp.arange(S)[None], s, -jnp.inf)
        return m.ein("bkgct,btkd->bckgd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (jnp.moveaxis(q, 1, 0), jnp.arange(S // qb)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, heads, head_dim)
    return m.ein("bshk,hkd->bsd", o, p["wo"])


def swiglu(m: Math, p, x):
    g = m.ein("bsd,df->bsf", x, p["w_gate"])
    u = m.ein("bsd,df->bsf", x, p["w_up"])
    return m.ein("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"])


def block(m: Math, lp, x, a: dict, *, causal):
    """Pre-norm residual block: x + attn(norm1 x), then + mlp(norm2 x).
    ``a`` holds the widths (heads, kv_heads, head_dim, theta, eps)."""
    h = rms_norm(x, lp["norm1"]["scale"], a["eps"])
    x = x + attention(m, lp["attn"], h, heads=a["heads"],
                      kv_heads=a["kv_heads"], head_dim=a["head_dim"],
                      theta=a["theta"], causal=causal)
    h = rms_norm(x, lp["norm2"]["scale"], a["eps"])
    return x + swiglu(m, lp["mlp"], h)


def stack(m: Math, layers, x, a: dict, *, causal):
    """The blocks of a [L, ...]-stacked layer tree, one after another, each
    recomputed in the backward pass."""
    fn = jax.checkpoint(lambda lp, x: block(m, lp, x, a, causal=causal))
    L = jax.tree_util.tree_leaves(layers)[0].shape[0]
    for i in range(L):
        x = fn(jax.tree_util.tree_map(lambda w: w[i], layers), x)
    return x


def cross_entropy(logits, labels, mask):
    """Masked mean of -log softmax(logits)[label]."""
    lp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(lp, labels[..., None], -1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --------------------------------------------------------------------------- #
# AdamW with global-norm clipping (Loshchilov & Hutter; decoupled decay)
# --------------------------------------------------------------------------- #
def adamw_init(params):
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"t": jnp.zeros((), jnp.float32), "mu": z,
            "nu": jax.tree_util.tree_map(jnp.zeros_like, params)}


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(
        tree)))


def adamw_step(params, state, grads, opt: dict):
    """One AdamW step.  ``opt``: lr, b1, b2, eps, weight_decay, clip_norm."""
    gn = global_norm(grads)
    scale = jnp.where(gn > opt["clip_norm"], opt["clip_norm"] / gn, 1.0)
    t = state["t"] + 1
    b1, b2 = opt["b1"], opt["b2"]

    def upd(p, g, mu, nu):
        g = g * scale
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        step = (mu / (1 - b1 ** t)) / (jnp.sqrt(nu / (1 - b2 ** t))
                                       + opt["eps"])
        return p - opt["lr"] * (step + opt["weight_decay"] * p), mu, nu

    out = jax.tree_util.tree_map(upd, params, grads, state["mu"],
                                 state["nu"])
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), {"t": t, "mu": pick(1), "nu": pick(2)}
