"""AdamW with fp32 master weights and ZeRO-shardable state.

State layout mirrors the parameter tree:  ``mu``/``nu``/``master`` get the
parameter's sharding spec *extended over free mesh axes* (ZeRO) by
``repro.dist.sharding.opt_state_shardings``.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class DonatedStateError(RuntimeError):
    """A params/optimizer-state tree holds deleted (donated) buffers.

    The step jits donate their optimizer-state argument, and
    ``jax.device_put`` is a no-copy identity when the target sharding
    already matches — so "fresh" state derived from a tree a previous
    step donated can silently alias the dead buffers and crash deep
    inside the next compiled call.
    """


def deleted_leaf_paths(tree) -> list:
    """Keypaths of every leaf of ``tree`` deleted by a donating jit.
    Tracers and array-likes without real buffers are skipped, so this is
    safe to call from inside jitted update fns (returns []).  The
    donation linter (``repro.analysis.donation``) builds on this to lint
    whole runtimes instead of single trees."""
    dead = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        deleted = getattr(leaf, "is_deleted", None)
        if not callable(deleted):
            continue
        try:
            if deleted():
                dead.append(jax.tree_util.keystr(path))
        except Exception:      # tracer / array-like without real buffers
            continue
    return dead


def check_live(tree, what: str = "optimizer state") -> None:
    """Raise :class:`DonatedStateError` if any leaf of ``tree`` was
    deleted by a donating jit.  A no-op under tracing (tracers carry no
    buffers), so it is safe to call from inside jitted update fns."""
    dead = deleted_leaf_paths(tree)
    if dead:
        raise DonatedStateError(
            f"{what} contains deleted (donated) buffers (first dead "
            f"leaf: {dead[0]!r}) — this tree was consumed by a previous "
            "donating update step. Re-`place` fresh state "
            "(CompoundRuntime.place / jax.device_put of a host copy) "
            "instead of re-using a tree that has already been donated.")


class AdamWState(NamedTuple):
    step: jnp.ndarray            # scalar int32
    mu: Any                      # fp32 tree
    nu: Any                      # fp32 tree
    master: Any                  # fp32 master weights


class AdamWConfig(NamedTuple):
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def init(params) -> AdamWState:
    # copy=True: master must never alias params (donation safety)
    f32 = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.array(x, jnp.float32, copy=True), t)
    zeros = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), params)
    return AdamWState(jnp.zeros((), jnp.int32), zeros,
                      jax.tree_util.tree_map(jnp.copy, zeros), f32(params))


def state_specs(param_specs):
    """ShapeDtypeStruct tree of the state given param ShapeDtypeStructs."""
    f32 = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), param_specs)
    return AdamWState(jax.ShapeDtypeStruct((), jnp.int32), f32, f32, f32)


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def update(grads, state: AdamWState, lr: jnp.ndarray,
           cfg: AdamWConfig = AdamWConfig(), param_dtype=jnp.bfloat16,
           gnorm=None):
    """One AdamW step. Returns (new_params_in_param_dtype, new_state,
    grad_norm).

    ``gnorm`` overrides the clip norm with a precomputed value — the
    disaggregated runtimes pass the *joint* norm across all sections so
    per-section updates clip exactly like one colocated update would.
    Passing it with clipping disabled raises: the caller clearly expects
    the joint norm to drive the update, and it would be silently ignored.
    Its device ops carry the scope ``optimizer``.
    """
    check_live(state, "optimizer state")
    if gnorm is not None and cfg.clip_norm <= 0:
        raise ValueError(
            f"adamw.update: gnorm= was passed but clipping is disabled "
            f"(clip_norm={cfg.clip_norm}) — the precomputed joint norm "
            "would be silently ignored; enable clip_norm or drop gnorm=")
    with jax.named_scope("optimizer"):
        if gnorm is None:
            gnorm = global_norm(grads)
        scale = jnp.where(gnorm > cfg.clip_norm, cfg.clip_norm / gnorm, 1.0) \
            if cfg.clip_norm > 0 else jnp.float32(1.0)
        step = state.step + 1
        t = step.astype(jnp.float32)
        bc1 = 1.0 - cfg.b1 ** t
        bc2 = 1.0 - cfg.b2 ** t

        def upd(g, mu, nu, m):
            g = g.astype(jnp.float32) * scale
            mu = cfg.b1 * mu + (1 - cfg.b1) * g
            nu = cfg.b2 * nu + (1 - cfg.b2) * jnp.square(g)
            mu_hat = mu / bc1
            nu_hat = nu / bc2
            step_v = mu_hat / (jnp.sqrt(nu_hat) + cfg.eps)
            m = m - lr * (step_v + cfg.weight_decay * m)
            return mu, nu, m

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_mu = treedef.flatten_up_to(state.mu)
        flat_nu = treedef.flatten_up_to(state.nu)
        flat_ma = treedef.flatten_up_to(state.master)
        out = [upd(g, mu, nu, m) for g, mu, nu, m in
               zip(flat_g, flat_mu, flat_nu, flat_ma)]
        mu = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
        nu = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
        master = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
        new_params = jax.tree_util.tree_map(
            lambda m, g: m.astype(g.dtype) if g.dtype != jnp.float32 else m,
            master, grads)
        return new_params, AdamWState(step, mu, nu, master), gnorm
