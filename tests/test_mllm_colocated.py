"""The colocated MLLM step skips the ViT on microbatches that hold no
image: its loss and gradients equal a step that runs the ViT on every
microbatch, ``vit_mbs`` counts the microbatches whose ViT ran, and the
compiled step keeps the branch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cost_model
from repro.core.executor import order_samples
from repro.core.types import ParallelConfig
from repro.launch.mesh import make_mesh
from repro.mllm import workload as mllm
from repro.models import vlm
from repro.models.model import build_model
from repro.optim import adamw
from test_chip_smoke import tiny_configs

B, MBS, SEQ = 8, 2, 32

#: per-sample image flags; with MBS 2 the wavefront order puts the images
#: first, so "mixed" gives one microbatch of two images, one of an image
#: and a text sample, and two with no image
BATCHES = {"mixed": [0, 1, 0, 0, 1, 0, 1, 0],
           "all_text": [0] * B,
           "all_image": [1] * B}


@pytest.fixture(scope="module")
def setup():
    vit, lm = tiny_configs()
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    step, shard = mllm.build_colocated_step(vit, lm, mesh, mbs=MBS,
                                            seq_len=SEQ, impl="ref",
                                            return_grads=True)
    graph = mllm.mllm_spec(vit, lm, vit_parallel=ParallelConfig(),
                           lm_parallel=ParallelConfig(), global_batch=B,
                           seq_len=SEQ, mbs=MBS).to_graph()
    return vit, lm, step, shard, graph


def host_batch(vit, lm, has, seed=0):
    rng = np.random.default_rng(seed)
    has = np.asarray(has, bool)
    K = lm.max_image_tokens
    toks = rng.integers(0, lm.vocab_size, (B, SEQ + 1))
    patches = rng.standard_normal(
        (B, K * vlm.downsample_factor(vit), vit.frontend_dim)
    ).astype(np.float32)
    patches[~has] = 0.0
    mask = np.ones((B, SEQ), np.float32)
    mask[has, :K] = 0.0
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32),
            "loss_mask": mask, "patches": patches,
            "image_pos": np.tile(np.arange(K, dtype=np.int32), (B, 1)),
            "image_valid": np.tile(has[:, None], (1, K)).astype(np.int32),
            "has_image": has.astype(np.int32)}


def wavefront_plan(graph, has):
    samples = cost_model.sample_tuples(graph, {"vit": has}, SEQ, n=len(has))
    order, sched = order_samples(samples, reorder=True)
    return mllm.build_plan(order, has, MBS, sched)


def unconditional_step(vit, lm, params, laid):
    """Loss and gradients with the ViT run on every microbatch, summed in
    f32 in dispatch order as the step sums them."""
    model = build_model(lm, impl="ref")

    def mb_loss(ps, mb, vidx, vval):
        sub = mb["patches"][vidx]
        emb = mllm.vit_forward(ps["vit"], vit, sub, vval, impl="ref")
        return mllm.lm_microbatch_loss(ps["lm"], model, mb, emb, vidx)

    def run(params, laid):
        n_mb = laid["tokens"].shape[0]

        def body(carry, xs):
            g_acc, l_acc = carry
            loss, g = jax.value_and_grad(mb_loss)(params, *xs)
            g_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (g_acc, l_acc + loss), None

        g0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)
        mbs_tree = {k: laid[k] for k in mllm.LM_KEYS + ("patches",)}
        (g_acc, l_acc), _ = jax.lax.scan(
            body, (g0, jnp.float32(0)),
            (mbs_tree, laid["vis_idx"], laid["vis_valid"]))
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / n_mb).astype(p.dtype), g_acc, params)
        return l_acc / n_mb, grads

    return jax.jit(run)(params, laid)


@pytest.mark.parametrize("kind", list(BATCHES))
def test_skipping_the_vit_keeps_loss_and_grads(setup, kind):
    vit, lm, step, shard, graph = setup
    has = np.asarray(BATCHES[kind], bool)
    plan = wavefront_plan(graph, has)
    if kind == "mixed":
        assert 0 < len(plan.image_mbs) < plan.n_mb
    laid = jax.device_put(mllm.colocated_batch(host_batch(vit, lm, has),
                                               plan), shard["batch"])
    params = mllm.init_compound_params(vit, lm, jax.random.PRNGKey(0),
                                       shard["params"])
    want_loss, want_grads = unconditional_step(vit, lm, params, laid)
    opt = jax.device_put(adamw.init(params), shard["opt"])
    _, _, m = step(params, opt, laid, jnp.int32(0))

    assert int(m["vit_mbs"]) == len(plan.image_mbs)
    assert int(m["vit_mbs"]) == {"mixed": 2, "all_text": 0,
                                 "all_image": plan.n_mb}[kind]
    np.testing.assert_allclose(float(m["loss"]), float(want_loss),
                               rtol=1e-6)
    flat_got = jax.tree_util.tree_leaves_with_path(m["grads"])
    flat_want = jax.tree_util.tree_leaves(want_grads)
    for (path, got), want in zip(flat_got, flat_want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
    vit_g = jax.tree_util.tree_leaves(m["grads"]["vit"])
    assert any(np.abs(np.asarray(g)).max() > 0 for g in vit_g) == \
        (kind != "all_text")


def test_compiled_step_keeps_the_vit_branch(setup):
    vit, lm, step, shard, graph = setup
    has = np.asarray(BATCHES["mixed"], bool)
    laid = jax.device_put(
        mllm.colocated_batch(host_batch(vit, lm, has),
                             wavefront_plan(graph, has)), shard["batch"])
    params = jax.eval_shape(
        lambda k: mllm.init_compound_params(vit, lm, k),
        jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw.init, params)
    text = step.lower(params, opt, laid, jnp.int32(0)).compile().as_text()
    assert "conditional(" in text
