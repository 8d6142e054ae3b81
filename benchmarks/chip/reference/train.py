"""The reference's training loop, shared by every configuration: weights
from the seed, gradients accumulated over microbatches in float32, AdamW,
and the readings the benchmark compares with the program's."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common as rc
from .. import gen


def leaf_norms(tree) -> dict:
    """{key path: float32 L2 norm} of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms)}


def initial(layout, seed: int, part=None):
    """The seed's weights as the program is given them (bfloat16), held in
    float32."""
    w = gen.draw_weights(layout, seed, jnp.bfloat16, part=part)
    return jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t), donate_argnums=0)(w)


def train(loss_fn, layout, seed: int, mb_lists, opt: dict,
          train_key=None) -> dict:
    """``len(mb_lists)`` AdamW steps of ``loss_fn(trained, frozen, mb)``,
    each the mean over its microbatches.  ``train_key`` names the subtree
    that trains (the rest is frozen); None trains everything.

    Returns {"loss": [per step], "grad": {leaf: the first step's gradient
    before clipping, on the host}, "update": {leaf: norm of the change
    after the last step}}."""
    params = initial(layout, seed)
    if train_key is None:
        trained, frozen = params, None
    else:
        trained, frozen = params.pop(train_key), params
    state = rc.adamw_init(trained)

    # every buffer that a call replaces is donated: at the configurations'
    # sizes the float32 state fills most of a chip
    @functools.partial(jax.jit, donate_argnums=(2,))
    def acc(p, fr, gsum, lsum, mb):
        loss, g = jax.value_and_grad(loss_fn)(p, fr, mb)
        return jax.tree_util.tree_map(jnp.add, gsum, g), lsum + loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def mean(gsum, lsum, n):
        return jax.tree_util.tree_map(lambda g: g / n, gsum), lsum / n

    step = jax.jit(lambda p, s, g: rc.adamw_step(p, s, g, opt),
                   donate_argnums=(0, 1))
    losses, first_grad = [], None
    for mbs in mb_lists:
        gsum = jax.tree_util.tree_map(jnp.zeros_like, trained)
        lsum = jnp.float32(0)
        for mb in mbs:
            gsum, lsum = acc(trained, frozen, gsum, lsum, mb)
        grads, loss = mean(gsum, lsum, float(len(mbs)))
        del gsum
        losses.append(float(loss))
        if first_grad is None:
            first_grad = {jax.tree_util.keystr(p): np.asarray(
                jax.device_get(g), np.float32) for p, g in
                jax.tree_util.tree_flatten_with_path(grads)[0]}
        trained, state = step(trained, state, grads)
        del grads
    del state, frozen
    start = initial(layout, seed, train_key)
    update = leaf_norms(jax.tree_util.tree_map(jnp.subtract, trained,
                                               start))
    return {"loss": losses, "grad": first_grad, "update": update}
