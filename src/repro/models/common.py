"""Common model building blocks: param-spec registry, norms, RoPE, inits.

The framework is pure JAX (no flax).  Every model defines a *param-spec tree*:
a nested dict whose leaves are :class:`ParamSpec` — (shape, dtype, logical
axes, init).  From that single source of truth we derive

* materialized parameters         (``init_params``)
* ``jax.ShapeDtypeStruct`` stand-ins for the dry-run (``param_shapes``)
* ``NamedSharding`` trees          (``repro.dist.sharding``)
"""
from __future__ import annotations

import math
import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class ParamSpec(NamedTuple):
    shape: tuple
    axes: tuple            # logical axis name (or None) per dim; len == ndim
    init: str = "normal"   # normal | zeros | ones | scaled | embed
    dtype: Any = jnp.bfloat16
    fan_in_dims: tuple = ()   # dims contracted at use time (for scaled init)


def _leaf_is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_map_specs(fn: Callable[[ParamSpec], Any], tree):
    return jax.tree_util.tree_map(fn, tree, is_leaf=_leaf_is_spec)


def param_shapes(spec_tree):
    """ShapeDtypeStruct tree — no allocation; used by the dry-run."""
    return tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), spec_tree)


def _init_one(spec: ParamSpec, key) -> jnp.ndarray:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "embed":
        # 1/sqrt(d) keeps tied-unembedding logits O(1) at init
        std = 1.0 / math.sqrt(spec.shape[-1])
        return (jax.random.normal(key, spec.shape, jnp.float32) * std
                ).astype(spec.dtype)
    # scaled / normal: truncated-normal with fan-in scaling
    fan_in = 1
    dims = spec.fan_in_dims or tuple(range(max(len(spec.shape) - 1, 1)))
    for d in dims:
        fan_in *= spec.shape[d]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, spec.shape, jnp.float32)
            * std).astype(spec.dtype)


def _draw(leaves, treedef, key):
    keys = jax.random.split(key, len(leaves))
    vals = [_init_one(s, k) for s, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def init_params(spec_tree, key, shardings=None):
    """Materialize ``spec_tree`` from ``key`` in one jit.

    ``shardings`` (a tree matching ``spec_tree``, or one sharding for every
    leaf) draws each leaf straight into its placement; without it the tree
    lands on the default device.  One jit keeps the f32 draws fused, where
    leaf-by-leaf eager draws leave them pending on the default device."""
    leaves, treedef = jax.tree_util.tree_flatten(spec_tree,
                                                 is_leaf=_leaf_is_spec)
    draw = jax.jit(_draw, static_argnums=(0, 1), out_shardings=shardings)
    return draw(tuple(leaves), treedef, key)


def stack_specs(spec_tree, n: int, axis_name: str = "layers"):
    """Prepend a stacked `layers` dim to every spec (for scan-over-layers)."""
    return tree_map_specs(
        lambda s: ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.init,
                            s.dtype,
                            tuple(d + 1 for d in s.fan_in_dims)),
        spec_tree)


# --------------------------------------------------------------------------- #
# Activation-sharding hook (set by the step builder under a mesh context;
# models call shard_act at section boundaries so per-section sharding
# constraints reach inside scan bodies)
# --------------------------------------------------------------------------- #
import contextlib

_ACT_HOOK = None
_KERNEL_LAYOUT = None


class KernelLayout(NamedTuple):
    """The mesh a step shards activations over, and the mesh axes of the
    batch and head dims (None where unsharded)."""
    mesh: Any
    batch: Any
    heads: Optional[str]


def shard_act(x, kind: str):
    return _ACT_HOOK(x, kind) if _ACT_HOOK is not None else x


@contextlib.contextmanager
def act_hook(fn, kernel_layout: Optional[KernelLayout] = None):
    """Install the activation hook ``fn`` and the layout that
    :func:`per_shard` runs Pallas kernels under."""
    global _ACT_HOOK, _KERNEL_LAYOUT
    prev = _ACT_HOOK, _KERNEL_LAYOUT
    _ACT_HOOK, _KERNEL_LAYOUT = fn, kernel_layout
    try:
        yield
    finally:
        _ACT_HOOK, _KERNEL_LAYOUT = prev


_UNSPLIT_WARNED: set = set()


def per_shard(fn: Callable, *xs):
    """``fn(*xs)`` on [B, S, H, D] operands, run per (batch, head) shard
    of the installed kernel layout's mesh.

    The TPU compiler cannot partition a Pallas (Mosaic) kernel, so a
    kernel call in a step jitted over several devices goes through a
    ``shard_map``.  A dim is split only where every operand divides its
    mesh axes; otherwise every device runs the whole dim, and a warning
    names it once.  Without a layout, or on one device, this is
    ``fn(*xs)``."""
    layout = _KERNEL_LAYOUT
    if layout is None or layout.mesh.size == 1:
        return fn(*xs)
    sizes = dict(layout.mesh.shape)

    def axis(ax, dim):
        names = () if ax is None else (ax if isinstance(ax, tuple)
                                       else (ax,))
        n = math.prod(sizes[a] for a in names)
        if all(x.shape[dim] % n == 0 for x in xs):
            return ax if names else None
        shapes = tuple(x.shape for x in xs)
        if (shapes, dim) not in _UNSPLIT_WARNED:
            _UNSPLIT_WARNED.add((shapes, dim))
            warnings.warn(f"per_shard: dim {dim} of {shapes} does not "
                          f"divide mesh axes {names} ({n}); every device "
                          "runs the kernel over the whole dim",
                          stacklevel=3)
        return None

    spec = jax.sharding.PartitionSpec(axis(layout.batch, 0), None,
                                      axis(layout.heads, 2), None)
    return jax.shard_map(fn, mesh=layout.mesh, in_specs=(spec,) * len(xs),
                         out_specs=spec, check_vma=False)(*xs)


# --------------------------------------------------------------------------- #
# Gradient-dtype barrier
# --------------------------------------------------------------------------- #
# fp32 casts inside norms/activations (for numerics) silently PROMOTE the
# whole backward pass to fp32: the cotangent of `x.astype(f32)` w.r.t. a
# bf16 x is fp32, and it stays fp32 through every transpose-einsum below —
# doubling activation-grad HBM traffic and TP all-reduce bytes (measured:
# fp32 [mbs,S,D] all-reduces dominating qwen2.5/mixtral collective terms,
# EXPERIMENTS.md §Perf).  This identity op casts the cotangent back to the
# primal dtype, keeping forward numerics (fp32 accumulate) unchanged.
@jax.custom_vjp
def grad_dtype_barrier(x):
    return x


def _gdb_fwd(x):
    # residual: zero-size array carrying only the primal dtype
    return x, jnp.zeros((0,), x.dtype)


def _gdb_bwd(res, g):
    return (g.astype(res.dtype),)


grad_dtype_barrier.defvjp(_gdb_fwd, _gdb_bwd)


# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = grad_dtype_barrier(x)
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps):
    x = grad_dtype_barrier(x)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def norm_spec(d: int) -> ParamSpec:
    return ParamSpec((d,), ("embed_nosplit",), "ones")


# --------------------------------------------------------------------------- #
# Rotary position embeddings
# --------------------------------------------------------------------------- #
def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq]."""
    x = grad_dtype_barrier(x)
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta))        # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]                     # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Misc
# --------------------------------------------------------------------------- #
def dense(x: jnp.ndarray, w: jnp.ndarray, contract: int = 1) -> jnp.ndarray:
    """x @ w contracting the last `contract` dims of x with the first of w."""
    nx, nw = x.ndim, w.ndim
    return jax.lax.dot_general(
        x, w,
        (((tuple(range(nx - contract, nx))), tuple(range(contract))), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


def softmax_fp32(logits: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    return jax.nn.softmax(logits.astype(jnp.float32), axis=axis)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy in fp32. logits [..., V], labels [...]."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)
