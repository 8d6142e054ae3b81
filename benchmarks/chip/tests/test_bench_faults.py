"""A run at a tiny size on the CPU with the timed path broken underneath
comes out not correct, under the cells' own limits: once for each fault a
one-chip training cell can have."""
import numpy as np
import pytest

from benchmarks.chip.tests.fixtures import run_cell, small  # noqa: F401


def _state_unchanged(monkeypatch):
    """AdamW hands back its state as it was (step count aside)."""
    import jax
    from repro.optim import adamw
    real = adamw.update

    def update(grads, state, lr, cfg=adamw.AdamWConfig(), **kw):
        _, _, gnorm = real(grads, state, lr, cfg, **kw)
        params = jax.tree_util.tree_map(lambda m, g: m.astype(g.dtype),
                                        state.master, grads)
        return params, state._replace(step=state.step + 1), gnorm
    monkeypatch.setattr(adamw, "update", update)


def _half_batch(monkeypatch):
    """The second half of each batch's microbatches repeats the first, so
    the step's mean is over half of the batch."""
    import jax
    import jax.numpy as jnp
    from repro.mllm import workload as mllm
    from repro.train import step as train_step
    lay, split = mllm.colocated_batch, train_step._split_microbatches

    def halve(x):
        n = x.shape[0] // 2
        return jnp.concatenate([x[:n], x[:n]]) if n else x

    def colocated_batch(batch, plan):
        out = lay(batch, plan)
        return {k: halve(v) if k not in ("vis_idx", "vis_valid") else
                jnp.concatenate([v[:len(v) // 2]] * 2) for k, v in
                out.items()}

    def split_microbatches(batch, n_micro, dp_total):
        return jax.tree_util.tree_map(halve, split(batch, n_micro,
                                                   dp_total))
    monkeypatch.setattr(mllm, "colocated_batch", colocated_batch)
    monkeypatch.setattr(train_step, "_split_microbatches",
                        split_microbatches)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("which", ["mllm", "distill"])
def test_broken_path_is_not_correct(small, run_cell, monkeypatch, which,
                                   fault):
    name, _, _ = small(which)
    fault(monkeypatch)
    r = run_cell(name)
    assert not r["correct"], r["checks"]
    assert np.isfinite([c["value"] for c in r["checks"].values()]).all()


def test_wrong_pairing_is_not_correct(small, run_cell, monkeypatch):
    """The scheduler hands back a permutation that pairs each image sample
    with a text sample: the reference groups the batch by its own rule, so
    the run is not correct.  Image samples hold 24 of 64 positions here,
    so that the two groupings weigh the encoder's gradient apart by about
    as much as the cell's 1024 of 4096 do."""
    from repro.core import executor
    from benchmarks.chip.reference import mllm as ref
    name, c, _ = small("mllm")
    c["max_image_tokens"] = 24
    real = executor.order_samples

    def order_samples(samples, reorder=True):
        _, sched = real(samples, reorder=reorder)
        has = [s.t_f_bc > 0 for s in samples]
        return ref.interleaved_order(has), sched
    monkeypatch.setattr(executor, "order_samples", order_samples)
    r = run_cell(name)
    assert not r["correct"], r["checks"]
