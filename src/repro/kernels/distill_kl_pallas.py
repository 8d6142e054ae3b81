"""Chunked-vocab distillation KL as a Pallas TPU kernel.

Grid: (token_blocks, vocab_blocks) with vocab innermost (sequential on
TPU); six online accumulators (max/sumexp for both models + the two
teacher-weighted sums) live in VMEM scratch and the per-token statistics
are flushed at the last vocab step.  Neither model's [N, V] logits ever
exist in HBM — the kernel-level form of Maestro §3.1's "ship hidden
states, not logits".

VMEM working set per step: two weight tiles [D, block_v] (the dominant
term) and two hidden tiles [block_t, D], double-buffered, their f32
copies for the matmuls, and the f32 [block_t, block_v] logit tiles.
``_vmem_bytes`` counts it, and the block is halved until it fits
``_VMEM_BUDGET``; the kernel's scoped VMEM limit is raised to match (v5e's
default scoped limit is 16 MiB of 128 MiB).  At D=4096, bf16, block_t=256
that is block_v=256: 2·2·(2+2) MiB of tiles, 2·(4+4) MiB of f32 copies
and 2·0.25 MiB of logits, ≈ 32.5 MiB.

Backward: custom VJP reusing the analytic chunked backward from
``distill_kl.py`` (dz_s = p_s − p_t etc.), which never materializes logits
either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import distill_kl as _dk
from repro.kernels.lanes import col_to_row as _col_to_row

NEG_INF = -1e30
_VMEM_BUDGET = 48 * 2**20


def _kernel(hs_ref, ws_ref, ht_ref, wt_ref,
            lse_s_ref, lse_t_ref, e_t_ref, e_s_ref,
            ms_ref, ls_ref, mt_ref, lt_ref, ut_ref, us_ref, *,
            inv_temp, nv):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        ms_ref[...] = jnp.full_like(ms_ref, NEG_INF)
        mt_ref[...] = jnp.full_like(mt_ref, NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)
        lt_ref[...] = jnp.zeros_like(lt_ref)
        ut_ref[...] = jnp.zeros_like(ut_ref)
        us_ref[...] = jnp.zeros_like(us_ref)

    hs = hs_ref[...].astype(jnp.float32)
    ht = ht_ref[...].astype(jnp.float32)
    ws = ws_ref[...].astype(jnp.float32)
    wt = wt_ref[...].astype(jnp.float32)
    zs = jax.lax.dot_general(hs, ws, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * inv_temp
    zt = jax.lax.dot_general(ht, wt, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * inv_temp

    ms_prev = ms_ref[...]                                 # [bt, 1]
    ms_new = jnp.maximum(ms_prev, jnp.max(zs, axis=1, keepdims=True))
    ls_ref[...] = (ls_ref[...] * jnp.exp(ms_prev - ms_new)
                   + jnp.sum(jnp.exp(zs - ms_new), axis=1, keepdims=True))
    ms_ref[...] = ms_new

    mt_prev = mt_ref[...]
    mt_new = jnp.maximum(mt_prev, jnp.max(zt, axis=1, keepdims=True))
    corr = jnp.exp(mt_prev - mt_new)
    pt = jnp.exp(zt - mt_new)
    lt_ref[...] = lt_ref[...] * corr + jnp.sum(pt, axis=1, keepdims=True)
    ut_ref[...] = ut_ref[...] * corr + jnp.sum(pt * zt, axis=1,
                                               keepdims=True)
    us_ref[...] = us_ref[...] * corr + jnp.sum(pt * zs, axis=1,
                                               keepdims=True)
    mt_ref[...] = mt_new

    @pl.when(iv == nv - 1)
    def _finalize():
        ls = jnp.maximum(ls_ref[...], 1e-30)
        lt = jnp.maximum(lt_ref[...], 1e-30)
        lse_s_ref[...] = _col_to_row(ms_ref[...] + jnp.log(ls))
        lse_t_ref[...] = _col_to_row(mt_ref[...] + jnp.log(lt))
        e_t_ref[...] = _col_to_row(ut_ref[...] / lt)
        e_s_ref[...] = _col_to_row(us_ref[...] / lt)


def _vmem_bytes(bt, bv, Ds, Dt, itemsize):
    """Double-buffered input tiles, their f32 copies and the two f32
    logit tiles."""
    tiles = (Ds + Dt) * (bt + bv)
    return 2 * tiles * itemsize + tiles * 4 + 2 * bt * bv * 4


def _stats_pallas(h_s, w_s, h_t, w_t, *, temperature, block_t, block_v,
                  interpret):
    N, Ds = h_s.shape
    Dt = h_t.shape[1]
    V = w_s.shape[1]
    bt, bv = block_t, block_v
    assert N % bt == 0 and V % bv == 0, (N, V, bt, bv)
    grid = (N // bt, V // bv)
    kernel = functools.partial(_kernel, inv_temp=1.0 / temperature,
                               nv=V // bv)
    vmem = _vmem_bytes(bt, bv, Ds, Dt, w_s.dtype.itemsize)
    # statistics are written lane-dense as [1, N] rows
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, Ds), lambda it, iv: (it, 0)),
            pl.BlockSpec((Ds, bv), lambda it, iv: (0, iv)),
            pl.BlockSpec((bt, Dt), lambda it, iv: (it, 0)),
            pl.BlockSpec((Dt, bv), lambda it, iv: (0, iv)),
        ],
        out_specs=[pl.BlockSpec((1, bt), lambda it, iv: (0, it))] * 4,
        out_shape=[jax.ShapeDtypeStruct((1, N), jnp.float32)] * 4,
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)] * 6,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem + 8 * 2**20, 16 * 2**20)),
        interpret=interpret,
        name="distill_kl_stats",
    )(h_s, w_s, h_t, w_t)
    return [o[0] for o in outs]                # lse_s, lse_t, e_t, e_s


def fits(N, V, block_t=256, block_v=512) -> bool:
    """Whether the kernel takes N rows over a vocabulary of V: each a
    multiple of its block (or shorter than it)."""
    return N % min(block_t, N) == 0 and V % min(block_v, V) == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _distill_kl_p(h_s, w_s, h_t, w_t, mask, T, block_t, block_v,
                  interpret):
    lse_s, lse_t, e_t, e_s = _stats_pallas(
        h_s, w_s, h_t, w_t, temperature=T, block_t=block_t,
        block_v=block_v, interpret=interpret)
    return _dk._kl_from_stats(lse_s, lse_t, e_t, e_s, mask)


def _fwd(h_s, w_s, h_t, w_t, mask, T, block_t, block_v, interpret):
    lse_s, lse_t, e_t, e_s = _stats_pallas(
        h_s, w_s, h_t, w_t, temperature=T, block_t=block_t,
        block_v=block_v, interpret=interpret)
    out = _dk._kl_from_stats(lse_s, lse_t, e_t, e_s, mask)
    return out, (h_s, w_s, h_t, w_t, mask, lse_s, lse_t, e_t, e_s)


def _bwd(T, block_t, block_v, interpret, res, g):
    return _dk._distill_kl_bwd(T, block_v, res, g)


_distill_kl_p.defvjp(_fwd, _bwd)


def distill_kl_pallas(h_student, w_student, h_teacher, w_teacher, *,
                      mask=None, temperature: float = 1.0,
                      interpret: bool = False, block_t: int = 256,
                      block_v: int = 512):
    """Raises ``ValueError`` when N or V is not a multiple of its block;
    a caller that wants the jnp path asks ``kernels.ops`` for
    ``impl="ref"``.  ``block_v`` is halved (staying a divisor of V) until
    the VMEM working set fits ``_VMEM_BUDGET``."""
    N, Ds = h_student.shape
    Dt = h_teacher.shape[1]
    V = w_student.shape[1]
    bt = min(block_t, N)
    bv = min(block_v, V)
    if not fits(N, V, block_t, block_v):
        raise ValueError(
            f"distill_kl_pallas: N={N}, V={V} are not multiples of the "
            f"blocks block_t={bt}, block_v={bv}; pad them or use "
            f"impl='ref'")
    itemsize = jnp.dtype(w_student.dtype).itemsize
    while (_vmem_bytes(bt, bv, Ds, Dt, itemsize) > _VMEM_BUDGET
           and bv % 256 == 0):
        bv //= 2
    return _distill_kl_p(h_student, w_student, h_teacher, w_teacher, mask,
                         float(temperature), bt, bv, bool(interpret))
