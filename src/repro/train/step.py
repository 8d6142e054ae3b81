"""Step builders: jitted train / prefill / decode steps with shardings.

``build_train_step`` produces the exact function the multi-pod dry-run
lowers for ``train_*`` cells; ``build_prefill_step`` / ``build_decode_step``
cover the ``prefill_*`` / ``decode_*`` / ``long_*`` cells.

Microbatching (grad accumulation) follows the per-section ``mbs`` knob from
the paper: the global batch is laid out shard-major ``[dp, n_micro, mbs]``
so the reshape into microbatches is local to every data shard (no
collectives for data staging).

Dispatch rules (``parallel_regime``) — how a section's
``ParallelConfig(dp, tp, pp, cp)`` reaches the compiled step:

* ``dp`` / ``tp`` are carried by the mesh's ``data`` / ``model`` axes and
  realized through GSPMD sharding constraints (``repro.dist.sharding``).
* ``pp > 1`` (mesh ``pipe`` axis > 1) → **PP regime**: the loss is the
  stage-partitioned GPipe loss from ``repro.dist.pipeline.build_pp_loss``
  (microbatching happens inside the staged schedule); the step takes one
  ``value_and_grad`` of it instead of the grad-accumulation scan.
* ``cp > 1`` (mesh ``seq`` axis > 1) → **CP regime**: the plain step, with
  ``repro.dist.context.cp_attention`` installed as the model's attention
  implementation and activations sequence-sharded over ``seq``.
* ``ParallelConfig.pp``/``.cp`` must match the mesh's ``pipe``/``seq``
  sizes, and pp×cp is unsupported — both raise instead of silently
  training with the pipe/seq devices replicated (the pre-PR-2 bug).
* ``ParallelConfig.grad_compress`` ∈ {"none", "bf16", "int8"} compresses
  the DP gradient all-reduce (``repro.optim.compression``): the loss +
  grad computation moves into a shard_map over the data axis, each shard
  accumulates its local microbatch gradients uncompressed in fp32, and
  ONE compressed all-reduce per step replaces the fp32 one (int8 carries
  an error-feedback residual across steps — the step gains a trailing
  ``ef`` argument/result, stacked ``[dp, ...]`` and donated).  Plain
  regime with a single data axis only; pp/cp/tp meshes raise.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.types import ArchConfig, ParallelConfig, ShapeConfig
from repro.dist import sharding as shd
from repro.models import attention as att
from repro.models import common as cm
from repro.models.model import Model
from repro.optim import adamw, schedules


def parallel_regime(mesh: Mesh, parallel: ParallelConfig) -> str:
    """Validate ``parallel`` against the mesh and pick the step regime:
    ``"plain"`` | ``"cp"`` | ``"pp"`` (see module docstring).  Raises
    instead of letting a pp/cp > 1 config fall through to the replicated
    step unannounced."""
    sizes = dict(mesh.shape)
    pp = sizes.get(shd.AXIS_PIPE, 1)
    cp = sizes.get(shd.AXIS_SEQ, 1)
    if parallel.pp != pp:
        raise ValueError(
            f"ParallelConfig.pp={parallel.pp} does not match the mesh's "
            f"pipe axis ({pp}): a pp>1 section must run on a mesh carved "
            f"by section_mesh/carve_meshes, not fall back to replication")
    if parallel.cp != cp:
        raise ValueError(
            f"ParallelConfig.cp={parallel.cp} does not match the mesh's "
            f"seq axis ({cp}): a cp>1 section must run on a mesh carved "
            f"by section_mesh/carve_meshes, not fall back to replication")
    if pp > 1 and cp > 1:
        raise NotImplementedError(
            "pp×cp composition is not supported (CP's shard_map cannot "
            "nest inside the pipeline's); use pp×tp or cp×tp instead")
    from repro.dist.context import CP_MODES
    if parallel.cp_mode not in CP_MODES:
        raise ValueError(
            f"ParallelConfig.cp_mode={parallel.cp_mode!r}: expected one "
            f"of {CP_MODES}")
    if parallel.cp_impl not in ("auto", "pallas", "pallas_interpret",
                                "ref"):
        raise ValueError(
            f"ParallelConfig.cp_impl={parallel.cp_impl!r}: expected "
            f"auto/pallas/pallas_interpret/ref")
    if parallel.cp_overlap_chunks < 1:
        raise ValueError(
            f"ParallelConfig.cp_overlap_chunks="
            f"{parallel.cp_overlap_chunks}: must be >= 1")
    if parallel.cp_overlap_chunks > 1 and parallel.cp_mode in (
            "allgather", "ulysses_mqa"):
        raise ValueError(
            f"ParallelConfig.cp_overlap_chunks="
            f"{parallel.cp_overlap_chunks} only applies to the ulysses "
            f"mode's K/V a2a chain, but cp_mode={parallel.cp_mode!r} "
            f"was forced")
    return "pp" if pp > 1 else ("cp" if cp > 1 else "plain")


def _check_pp_cp_support(cfg: ArchConfig, regime: str) -> None:
    if regime == "pp" and cfg.family == "audio":
        raise NotImplementedError(
            "pipeline parallelism is not implemented for encoder-decoder "
            "(audio) sections — build_pp_loss stages tf.lm_specs stacks")
    if regime == "cp":
        if cfg.family == "audio":
            raise NotImplementedError(
                "context parallelism is not implemented for encoder-"
                "decoder (audio) sections (cross-attention)")
        if not any(cfg.is_attn_layer(i) for i in range(cfg.num_layers)):
            raise NotImplementedError(
                f"cp>1 on attention-free arch {cfg.name!r}: there is no "
                "attention to sequence-shard, the seq axis would be "
                "silently replicated")


def _act_hook_for(mesh: Mesh, batch_size: int, seq_len: int,
                  sequence_parallel: bool = False):
    dp = shd.dp_axes(mesh)
    bspec = shd.batch_spec(mesh, batch_size, seq_len)
    b_ax, s_ax = tuple(bspec)[0], tuple(bspec)[1]
    model_size = mesh.shape.get("model", 1)
    cp = dict(mesh.shape).get(shd.AXIS_SEQ, 1)
    if cp > 1 and s_ax is None and seq_len % cp == 0:
        # CP: keep activations sequence-sharded over the seq axis between
        # attention calls — cp_attention's shard_map in_specs match this
        # layout, so only attention itself reshards
        s_ax = shd.AXIS_SEQ
    # Megatron-style sequence parallelism: the residual stream between
    # blocks is sequence-sharded over the model axis, turning the per-layer
    # TP all-reduce pair into reduce-scatter + all-gather at half the bytes
    # (and keeping norms local)
    sp_ax = ("model" if sequence_parallel and s_ax is None
             and seq_len % model_size == 0 else s_ax)

    def hook(x, kind):
        if kind == "hidden" and x.ndim == 3:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(b_ax, sp_ax, None)))
        if kind == "logits" and x.ndim == 3:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(b_ax, s_ax, "model")))
        if kind == "attn_q" and x.ndim == 4:
            h_ax = "model" if x.shape[2] % model_size == 0 else None
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(b_ax, s_ax, h_ax, None)))
        if kind == "moe_dispatch" and x.ndim == 4:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(b_ax, None, None, None)))
        return x

    # Pallas kernels run per (batch, head) shard; under CP, attention
    # goes through cp_attention's own shard_map instead
    return hook, cm.KernelLayout(mesh, b_ax,
                                 "model" if model_size > 1 else None)


def num_microbatches(shape: ShapeConfig, mesh: Mesh,
                     parallel: ParallelConfig) -> int:
    """Grad-accumulation depth for this (shape × mesh × C^s) cell.

    Raises at build time when the global batch cannot be laid out as
    ``[dp_total, n_micro, mbs]`` — the pre-PR-2 behaviour silently
    *duplicated* the full batch into every microbatch instead."""
    dp_total = shd.axis_size(mesh, shd.dp_axes(mesh))
    denom = dp_total * parallel.mbs
    n = shape.global_batch // denom
    # undersized global batches (< dp_total*mbs) stay legal — the batch is
    # replicated / seq-sharded, not microbatched; anything larger must lay
    # out exactly as [dp_total, n_micro, mbs]
    if shape.global_batch > denom and shape.global_batch % denom:
        raise ValueError(
            f"global_batch={shape.global_batch} is not a multiple of "
            f"dp_total*mbs={dp_total}*{parallel.mbs}: grad accumulation "
            "would train on duplicated data with an inflated effective "
            "batch; adjust ShapeConfig.global_batch or ParallelConfig.mbs")
    return max(n, 1)


def _split_microbatches(batch: dict, n_micro: int, dp_total: int):
    """[GB, ...] -> [n_micro, GB/n_micro, ...] with shard-major layout so
    the split is local to each data shard.  Raises on non-divisible
    batches (never silently duplicates data)."""
    def split(x):
        gb = x.shape[0]
        mgb = gb // n_micro
        per = mgb // dp_total
        if per == 0 or gb % n_micro or mgb % dp_total:
            raise ValueError(
                f"cannot split batch dim {gb} into {n_micro} microbatches "
                f"× {dp_total} DP shards: global_batch must be a multiple "
                "of dp_total*mbs")
        y = x.reshape((dp_total, n_micro, per) + x.shape[1:])
        return jnp.swapaxes(y, 0, 1).reshape(
            (n_micro, mgb) + x.shape[1:])
    return jax.tree_util.tree_map(split, batch)


def build_train_step(model: Model, mesh: Mesh, parallel: ParallelConfig,
                     shape: ShapeConfig, *, rules=None,
                     lr_schedule=None,
                     opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig()):
    """Returns (jitted_step, shardings) — step(params, opt_state, batch,
    step_idx) -> (params, opt_state, metrics).

    Dispatches on the mesh's ``pipe``/``seq`` axes (see module docstring):
    plain / CP / PP regimes all yield loss and parameter updates matching
    the monolithic reference within fp32 tolerance (driver-verified)."""
    cfg = model.cfg
    regime = parallel_regime(mesh, parallel)
    _check_pp_cp_support(cfg, regime)
    compress = parallel.grad_compress or "none"
    if compress != "none":
        from repro.optim import compression as gcomp
        if compress not in gcomp.METHODS:
            raise ValueError(
                f"ParallelConfig.grad_compress={compress!r}: expected one "
                f"of {gcomp.METHODS}")
        sizes = dict(mesh.shape)
        if regime != "plain" or any(
                sizes.get(a, 1) > 1 for a in (shd.AXIS_PIPE, shd.AXIS_SEQ,
                                              shd.AXIS_MODEL)):
            raise NotImplementedError(
                "grad_compress requires the plain regime on a dp-only "
                "mesh: the compressed all-reduce runs in a shard_map over "
                "the data axis and cannot nest inside pp/cp schedules or "
                "compose with tp activation sharding")
        if len(shd.dp_axes(mesh)) > 1:
            raise NotImplementedError(
                "grad_compress supports a single data axis (got a multi-"
                "pod dp mesh); compress per pod or disable")
        _dp = shd.axis_size(mesh, shd.dp_axes(mesh))
        if shape.global_batch % max(_dp, 1):
            raise NotImplementedError(
                f"grad_compress needs the global batch "
                f"({shape.global_batch}) to divide the data axis ({_dp}) "
                "so every shard owns a real slice of the batch")
    if regime == "pp" and parallel.sequence_parallel:
        raise NotImplementedError(
            "sequence_parallel is a GSPMD activation-layout knob and "
            "cannot apply inside the PP regime's manual shard_map; "
            "disable it for pp>1 sections")
    specs = model.specs()
    rules = rules if rules is not None else shd.rules_for(cfg, mesh)
    p_shard = shd.param_shardings(specs, mesh, rules)
    o_shard = shd.opt_state_shardings(specs, mesh, rules,
                                      zero=parallel.zero_opt)
    batch_specs = model.input_specs(shape)
    b_shard = shd.data_shardings(mesh, batch_specs)
    dp_total = shd.axis_size(mesh, shd.dp_axes(mesh))
    n_micro = num_microbatches(shape, mesh, parallel)
    lr_fn = lr_schedule or functools.partial(
        schedules.warmup_cosine, peak_lr=3e-4, warmup_steps=100,
        total_steps=10_000)
    rep = shd.replicated(mesh)

    if regime == "pp":
        from repro.dist import pipeline as pl
        # the staged loss microbatches internally with the same shard-major
        # layout contract as _split_microbatches, and equals the monolithic
        # full-batch loss (CE globally normalized, MoE aux exact)
        pp_loss, _ = pl.build_pp_loss(
            cfg, mesh, n_micro, impl=model.impl, remat=model.remat,
            causal=(cfg.family != "vit"),
            mb_layout=pl.contiguous_microbatch)
        grad_fn = jax.value_and_grad(pp_loss)

        def train_step(params, opt_state, batch, step_idx):
            loss, grads = grad_fn(params, batch)
            lr = lr_fn(step_idx)
            new_params, new_opt, gnorm = adamw.update(grads, opt_state, lr,
                                                      opt_cfg)
            return new_params, new_opt, {"loss": loss.astype(jnp.float32),
                                         "grad_norm": gnorm, "lr": lr}
    else:
        hook = _act_hook_for(mesh, shape.global_batch // n_micro,
                             shape.seq_len,
                             sequence_parallel=parallel.sequence_parallel)
        if regime == "cp":
            from repro.dist import context as cpx
            cp_impl = cpx.cp_attention_impl(
                mesh, batch_axes=shd.dp_axes(mesh) or None,
                mode=parallel.cp_mode, impl=parallel.cp_impl,
                overlap_chunks=parallel.cp_overlap_chunks)
        else:
            cp_impl = None

        def loss_fn(p, mb):
            impl_ctx = (att.attention_impl(cp_impl) if cp_impl is not None
                        else contextlib.nullcontext())
            with cm.act_hook(*hook), impl_ctx:
                return model.loss(p, mb)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def train_step(params, opt_state, batch, step_idx):
            if n_micro == 1:
                (loss, metrics), grads = grad_fn(params, batch)
            else:
                mbs_tree = _split_microbatches(batch, n_micro, dp_total)

                def micro(carry, mb):
                    g_acc, l_acc = carry
                    (loss, _), grads = grad_fn(params, mb)
                    g_acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), g_acc,
                        grads)
                    return (g_acc, l_acc + loss), None

                g0 = jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), params)
                (g_sum, l_sum), _ = jax.lax.scan(micro,
                                                 (g0, jnp.float32(0)),
                                                 mbs_tree)
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g / n_micro).astype(p.dtype), g_sum,
                    params)
                loss = l_sum / n_micro
                metrics = {}
            lr = lr_fn(step_idx)
            new_params, new_opt, gnorm = adamw.update(grads, opt_state, lr,
                                                      opt_cfg)
            out_metrics = {"loss": loss.astype(jnp.float32),
                           "grad_norm": gnorm, "lr": lr}
            return new_params, new_opt, out_metrics

    if compress != "none":
        da = (shd.dp_axes(mesh) or (shd.AXIS_DATA,))[0]
        grad_fn = jax.value_and_grad(
            lambda p, mb: model.loss(p, mb), has_aux=True)

        def sharded_loss_grad(params, batch_local, ef_local):
            """Runs on one data shard: local microbatch grad accumulation
            (fp32, uncompressed), then the single compressed mean-reduce
            across the data axis.  ``ef_local`` is the shard's [1, ...]
            slice of the stacked error-feedback residual."""
            with cm.act_hook(None):
                if n_micro == 1:
                    (loss, _), g = grad_fn(params, batch_local)
                    g = jax.tree_util.tree_map(
                        lambda x: x.astype(jnp.float32), g)
                else:
                    local = jax.tree_util.tree_map(
                        lambda x: x.reshape(
                            (n_micro, x.shape[0] // n_micro) + x.shape[1:]),
                        batch_local)

                    def micro(carry, mb):
                        g_acc, l_acc = carry
                        (l, _), g = grad_fn(params, mb)
                        g_acc = jax.tree_util.tree_map(
                            lambda a, b: a + b.astype(jnp.float32),
                            g_acc, g)
                        return (g_acc, l_acc + l), None

                    g0 = jax.tree_util.tree_map(
                        lambda x: jnp.zeros(x.shape, jnp.float32), params)
                    (g_sum, l_sum), _ = jax.lax.scan(
                        micro, (g0, jnp.float32(0)), local)
                    g = jax.tree_util.tree_map(lambda x: x / n_micro,
                                               g_sum)
                    loss = l_sum / n_micro
            mean_loss = jax.lax.psum(loss, da) / dp_total
            ef = gcomp.ErrorFeedback(jax.tree_util.tree_map(
                lambda x: x[0], ef_local))
            red, new_ef = gcomp.ef_compress_tree(g, ef, da, compress)
            red = jax.tree_util.tree_map(
                lambda r, p: r.astype(p.dtype), red, params)
            new_ef_stacked = jax.tree_util.tree_map(
                lambda x: x[None], new_ef.residual)
            return mean_loss, red, new_ef_stacked

        run = shd.shard_map(
            sharded_loss_grad, mesh,
            (P(), jax.tree_util.tree_map(lambda _: P(da), b_shard),
             P(da)),
            (P(), P(), P(da)))

        def train_step(params, opt_state, batch, step_idx, ef):  # noqa: F811
            loss, grads, new_ef = run(params, batch, ef)
            lr = lr_fn(step_idx)
            new_params, new_opt, gnorm = adamw.update(grads, opt_state,
                                                      lr, opt_cfg)
            return (new_params, new_opt,
                    {"loss": loss.astype(jnp.float32),
                     "grad_norm": gnorm, "lr": lr}, new_ef)

        ef_shard = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P(da)), dict(p_shard))
        step = jax.jit(
            train_step,
            in_shardings=(p_shard, o_shard, b_shard, rep, ef_shard),
            out_shardings=(p_shard, o_shard,
                           {"loss": rep, "grad_norm": rep, "lr": rep},
                           ef_shard),
            donate_argnums=(0, 1, 4))
        # donation metadata for the analysis pass (repro.analysis
        # .donation.lint_step_fn): which argnums this jit consumes
        step._donates = (0, 1, 4)
        step._donates_label = "train_step[compressed](params, opt, ef)"

        def ef_init(params):
            """Zero-initialized stacked [dp, ...] error-feedback residual,
            placed on the data axis."""
            z = jax.tree_util.tree_map(
                lambda p: jnp.zeros((dp_total,) + p.shape, jnp.float32),
                params)
            return jax.device_put(z, ef_shard)

        shardings = {"params": p_shard, "opt": o_shard, "batch": b_shard,
                     "ef": ef_shard, "ef_init": ef_init}
        return step, shardings

    step = jax.jit(
        train_step,
        in_shardings=(p_shard, o_shard, b_shard, rep),
        out_shardings=(p_shard, o_shard,
                       {"loss": rep, "grad_norm": rep, "lr": rep}),
        donate_argnums=(0, 1))
    step._donates = (0, 1)
    step._donates_label = "train_step(params, opt)"
    shardings = {"params": p_shard, "opt": o_shard, "batch": b_shard}
    return step, shardings


def _check_no_pp_cp_serving(mesh: Mesh, kind: str) -> None:
    sizes = dict(mesh.shape)
    if sizes.get(shd.AXIS_PIPE, 1) > 1 or sizes.get(shd.AXIS_SEQ, 1) > 1:
        raise NotImplementedError(
            f"{kind} cells do not support pipe/seq mesh axes > 1: serving "
            "shards long contexts over the model axis instead "
            "(kv_cache_spec flash-decoding split)")


def build_prefill_step(model: Model, mesh: Mesh, shape: ShapeConfig, *,
                       rules=None):
    _check_no_pp_cp_serving(mesh, "prefill")
    specs = model.specs()
    rules = rules if rules is not None else shd.rules_for(model.cfg, mesh)
    p_shard = shd.param_shardings(specs, mesh, rules)
    batch_specs = model.input_specs(shape)
    b_shard = shd.data_shardings(mesh, batch_specs)
    cache_specs = model.cache_specs(shape)
    c_shard = shd.cache_shardings(mesh, cache_specs)
    hook = _act_hook_for(mesh, shape.global_batch, shape.seq_len)
    logits_shard = shd.logits_sharding(mesh, shape.global_batch,
                                       model.cfg.padded_vocab)

    def prefill_step(params, batch):
        with cm.act_hook(*hook):
            logits, cache = model.prefill(params, batch)
        return logits, cache

    step = jax.jit(prefill_step, in_shardings=(p_shard, b_shard),
                   out_shardings=(logits_shard, c_shard))
    step._donates = ()
    step._donates_label = "prefill_step"
    return step, {"params": p_shard, "batch": b_shard, "cache": c_shard}


def build_decode_step(model: Model, mesh: Mesh, shape: ShapeConfig, *,
                      rules=None):
    """serve_step for decode cells: one new token against a seq_len cache."""
    _check_no_pp_cp_serving(mesh, "decode")
    specs = model.specs()
    rules = rules if rules is not None else shd.rules_for(model.cfg, mesh)
    p_shard = shd.param_shardings(specs, mesh, rules)
    batch_specs = model.input_specs(shape)
    b_shard = shd.data_shardings(mesh, batch_specs)
    cache_specs = model.cache_specs(shape)
    c_shard = shd.cache_shardings(mesh, cache_specs)
    logits_shard = shd.logits_sharding(mesh, shape.global_batch,
                                       model.cfg.padded_vocab)
    rep = shd.replicated(mesh)

    def decode_step(params, cache, token, pos):
        logits, new_cache = model.decode(params, cache, token, pos)
        return logits, new_cache

    step = jax.jit(decode_step,
                   in_shardings=(p_shard, c_shard, b_shard["token"], rep),
                   out_shardings=(logits_shard, c_shard),
                   donate_argnums=(1,))
    step._donates = (1,)
    step._donates_label = "decode_step(cache)"
    return step, {"params": p_shard, "cache": c_shard,
                  "token": b_shard["token"]}
