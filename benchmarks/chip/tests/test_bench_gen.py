"""Inputs and weights come from the seed alone, and every seed carries
the same work."""
import jax
import numpy as np

from benchmarks.chip import gen, harness
from benchmarks.chip.reference import distill


def test_same_seed_same_traffic_and_fixed_image_count():
    kw = dict(batch=8, seq_len=64, vocab=256, vision_ratio=0.5,
              image_tokens=4, patch_dim=8, downsample=4)
    a = gen.batches("vlm", 2**40 + 3, 3, **kw)
    b = gen.batches("vlm", 2**40 + 3, 3, **kw)
    c = gen.batches("vlm", 3, 3, **kw)
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    for batch in a + c:
        has = batch["has_image"].astype(bool)
        assert has.sum() == 4
        assert (batch["loss_mask"][has, :4] == 0).all()
        assert not batch["patches"][~has].any()


def test_subtree_draws_match_the_whole_tree():
    c = harness.config("granite-3.0-8b-2b-distill")
    c["teacher"].update(num_layers=1, d_model=16, num_heads=2,
                        num_kv_heads=1, head_dim=8, d_ff=32, vocab_size=64)
    c["student"].update(num_layers=1, d_model=8, num_heads=2,
                        num_kv_heads=1, head_dim=4, d_ff=16, vocab_size=64)
    layout = distill.layout(c)
    whole = gen.draw_weights(layout, 2**33 + 5)
    for part in ("student", "teacher"):
        sub = gen.draw_weights(layout, 2**33 + 5, part=part)
        assert all(np.array_equal(x, y) for x, y in zip(
            jax.tree_util.tree_leaves(whole[part]),
            jax.tree_util.tree_leaves(sub)))
    i = gen.leaf_range(layout, "teacher")[3]
    assert np.array_equal(gen.draw_leaf(layout, 2**33 + 5, i),
                          jax.tree_util.tree_leaves(whole)[i])
    other = gen.draw_weights(layout, 5)
    assert not np.array_equal(whole["student"]["embed"],
                              other["student"]["embed"])
