"""Inputs and weights of a run, made from ``--seed`` and nothing else.

Traffic generators are copies of the repo's ``repro.data.synthetic``
(``_lm_ngram_tokens``, ``vlm_batches``), kept here so that a change to
the program cannot move the yardstick.  Two departures, both so that every
seed carries the same work in another order:

* a batch holds exactly ``round(vision_ratio * batch)`` image samples, at
  positions drawn from the seed (the original draws each sample's
  modality on its own, so the image count varied from batch to batch);
* the n-gram recurrence draws its noise up front, which gives the same
  distribution in a fraction of the host time.

Weights are drawn on the device in one jit, leaf by leaf from
``fold_in(key, leaf index)``, straight into the shardings they are used in.
The layout (shapes and init rule of every leaf) comes from the plain
reference, so the program and the reference start from the same values
without either taking anything from the other.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A NumPy generator for ``seed`` (any whole number) and a stream id."""
    return np.random.default_rng([seed % 2**63, stream])


def key_for(seed: int):
    """A JAX key for any whole number (``--seed`` may exceed 32 bits)."""
    s = seed % 2**63
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


# --------------------------------------------------------------------------- #
# Traffic
# --------------------------------------------------------------------------- #
def ngram_tokens(rng: np.random.Generator, batch: int, seq: int,
                 vocab: int) -> np.ndarray:
    """[batch, seq + 1] tokens: t+1 = (a*t + b) mod vocab with per-sequence
    (a, b), replaced by a uniform token with probability 0.1, so the loss is
    learnable."""
    a = rng.integers(1, 17, batch)
    b = rng.integers(0, vocab, batch)
    flip = rng.random((seq, batch)) < 0.1
    noise = rng.integers(0, vocab, (seq, batch))
    out = np.empty((seq + 1, batch), np.int64)
    out[0] = rng.integers(0, vocab, batch)
    for i in range(seq):
        out[i + 1] = np.where(flip[i], noise[i], (a * out[i] + b) % vocab)
    return out.T


def text_batch(rng: np.random.Generator, *, batch: int, seq_len: int,
               vocab: int) -> dict:
    toks = ngram_tokens(rng, batch, seq_len, vocab)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32),
            "loss_mask": np.ones((batch, seq_len), np.float32)}


def vlm_batch(rng: np.random.Generator, *, batch: int, seq_len: int,
              vocab: int, vision_ratio: float, image_tokens: int,
              patch_dim: int, downsample: int) -> dict:
    """One mixed text/vision batch: image samples carry ``image_tokens *
    downsample`` patches whose embeddings replace their first
    ``image_tokens`` positions, which carry no LM loss."""
    out = text_batch(rng, batch=batch, seq_len=seq_len, vocab=vocab)
    K, P = image_tokens, image_tokens * downsample
    has = np.zeros(batch, bool)
    has[rng.permutation(batch)[:round(vision_ratio * batch)]] = True
    patches = np.zeros((batch, P, patch_dim), ml_dtypes.bfloat16)
    for i in np.flatnonzero(has):
        patches[i] = rng.standard_normal((P, patch_dim), np.float32)
    out["loss_mask"][has, :K] = 0.0
    out.update(
        patches=patches,
        image_pos=np.tile(np.arange(K, dtype=np.int32), (batch, 1)),
        image_valid=np.tile(has[:, None], (1, K)).astype(np.int32),
        has_image=has.astype(np.int32))
    return out


GENERATORS = {"text": text_batch, "vlm": vlm_batch}


def batches(kind: str, seed: int, n: int, **kw) -> list:
    """``n`` distinct host batches of generator ``kind`` from ``seed``."""
    rng = rng_for(seed, 1)
    return [GENERATORS[kind](rng, **kw) for _ in range(n)]


# --------------------------------------------------------------------------- #
# Weights
# --------------------------------------------------------------------------- #
class Leaf(NamedTuple):
    """One parameter: its shape and how it is drawn.  ``init`` is ``ones``,
    ``zeros``, ``embed`` (std 1/sqrt(last dim)) or ``normal`` (std
    1/sqrt(product of the ``fan_in`` dims))."""
    shape: tuple
    init: str = "normal"
    fan_in: tuple = (0,)


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def _draw_leaf(leaf: Leaf, key, dtype):
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, dtype)
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, dtype)
    if leaf.init == "embed":
        std = 1.0 / math.sqrt(leaf.shape[-1])
    else:
        std = 1.0 / math.sqrt(math.prod(leaf.shape[d] for d in leaf.fan_in))
    return (jax.random.normal(key, leaf.shape, jnp.float32) * std
            ).astype(dtype)


def draw_tree(layout, key, dtype=jnp.bfloat16, part=None):
    """Traceable: the values of every leaf of ``layout`` (no jit), or of
    its subtree ``part`` alone, each leaf as the whole tree draws it."""
    leaves = jax.tree_util.tree_leaves(layout, is_leaf=is_leaf)
    treedef = jax.tree_util.tree_structure(
        layout if part is None else layout[part], is_leaf=is_leaf)
    return jax.tree_util.tree_unflatten(treedef, [
        _draw_leaf(leaves[i], jax.random.fold_in(key, i), dtype)
        for i in leaf_range(layout, part)])


def draw_weights(layout, seed: int, dtype=jnp.bfloat16, shardings=None,
                 part=None):
    """All of ``layout`` (or its subtree ``part``) in one jit, placed by
    ``shardings`` (a matching tree, or None for the default device).  The
    values leave the jit in ``dtype``, so they are rounded to it: a
    conversion inside one jit may be skipped (XLA allows excess
    precision), one at its output may not."""
    fn = jax.jit(lambda k: draw_tree(layout, k, dtype, part),
                 out_shardings=shardings)
    return fn(key_for(seed))


def draw_leaf(layout, seed: int, index: int, dtype=jnp.bfloat16):
    """Leaf ``index`` (in ``layout``'s flattened order) alone, as
    ``draw_weights`` draws it."""
    leaf = jax.tree_util.tree_leaves(layout, is_leaf=is_leaf)[index]
    return jax.jit(lambda k: _draw_leaf(leaf, jax.random.fold_in(k, index),
                                        dtype))(key_for(seed))


def leaf_range(layout, part=None) -> range:
    """Flattened indices of the leaves of ``layout``, or of its subtree
    ``part``."""
    def count(tree):
        return len(jax.tree_util.tree_leaves(tree, is_leaf=is_leaf))
    if part is None:
        return range(count(layout))
    first = sum(count(layout[k]) for k in sorted(layout) if k < part)
    return range(first, first + count(layout[part]))


def shapes(layout):
    return jax.tree_util.tree_map(lambda leaf: tuple(leaf.shape), layout,
                                  is_leaf=is_leaf)
