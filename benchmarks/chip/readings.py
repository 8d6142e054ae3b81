"""The program's side of the correctness readings, taken from the state a
training step leaves (before the next step, which donates it, runs)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import gen


def _flat(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [jax.tree_util.keystr(p) for p, _ in flat], [x for _, x in flat]


def first_grads(opt_state, grad_norm, opt: dict) -> dict:
    """The first step's gradient before clipping, as the optimizer got it,
    leaf by leaf on the host in float32: its first moment after one step
    is ``(1 - b1) * clip_scale * grad``."""
    keys, mus = _flat(opt_state.mu)
    unscale = max(1.0, float(grad_norm) / opt["clip_norm"]) / (1 - opt["b1"])
    return {k: np.asarray(jax.device_get(x), np.float32) * np.float32(unscale)
            for k, x in zip(keys, mus)}


def update_norms(master, layout, seed: int, part=None) -> dict:
    """Per-leaf norms of the float32 master weights' change since the
    seed's weights, drawn again on the device as they were given, one leaf
    at a time so that the readings add no more than a leaf to the run's
    peak memory; ``part`` names the subtree of ``layout`` that trains."""
    keys, ms = _flat(master)
    norm = jax.jit(lambda x, w: jnp.sqrt(jnp.sum(jnp.square(
        x - w.astype(jnp.float32)))))
    return {k: float(norm(x, gen.draw_leaf(layout, seed, i)))
            for k, x, i in zip(keys, ms, gen.leaf_range(layout, part),
                               strict=True)}


def check_layout(layout, specs):
    """The reference's parameter tree must be the program's, leaf for
    leaf, or the two cannot start from the same weights."""
    from repro.models.common import tree_map_specs
    got = gen.shapes(layout)
    want = tree_map_specs(lambda s: tuple(s.shape), specs)
    if got != want:
        raise ValueError(f"parameter trees differ:\n{got}\n{want}")
