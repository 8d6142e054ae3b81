"""Flash attention as a Pallas TPU kernel.

TPU-native layout (not a CUDA port): the MXU wants [≥128 × 128] matmul
tiles, so blocks default to (block_q=512, block_kv=512) with head_dim as
the minor dimension; online-softmax statistics live in VMEM scratch across
the sequential innermost grid dimension (TPU grids execute in order, which
replaces the CUDA warp-level loop).

Grid: (batch, q_heads, nq, nkv) — the kv dimension is innermost; (m, l,
acc) scratch carries across it and the output/LSE tiles are flushed at the
final kv step.  GQA is expressed in the K/V index_map (kv_head = h // G) —
no KV duplication in HBM or VMEM.  Causal/sliding-window masking is
positional; fully-masked (above-diagonal) blocks skip their matmuls via
``pl.when``.

KV positions: both masks are difference-based (``q_pos >= kv_pos`` and
``q_pos - kv_pos < window``), so a shifted query window (decode q_offset,
CP allgather shards) and non-contiguous KV rows (chunked-ulysses a2a
output, which interleaves per-device sub-slices) are both expressed as an
explicit ``kv_positions`` int32 operand — one extra [1, T] input, loaded
per kv block; the block-skip condition then uses the block's position
min/max instead of the static grid arithmetic.

Backward: custom VJP over the blockwise-recompute backward in ``ref.py``
(identical math to the FlashAttention-2 backward; on TPU it lowers to the
same scan structure the forward uses).  Forward emits LSE for it.
``flash_attention_lse`` exposes the (o, lse) pair with a VJP that consumes
the lse cotangent, which makes :func:`merge_flash_partials` — the
online-softmax merge of partial results over disjoint KV chunks — exactly
differentiable end to end.

Validated in interpret mode on CPU against ``ref.mha_reference`` across a
shape/dtype sweep (tests/test_kernels_flash.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels.lanes import col_to_row as _col_to_row

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, window,
                block_q, block_kv, nkv, has_pos):
    if has_pos:
        pos_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        pos_ref = None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    if has_pos:
        kv_pos = pos_ref[...]                              # [1, bkv]
        kv_lo, kv_hi = jnp.min(kv_pos), jnp.max(kv_pos)
    else:
        kv_pos = ik * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        kv_lo, kv_hi = ik * block_kv, ik * block_kv + block_kv - 1
    run = True
    if causal:
        # skip blocks entirely above the diagonal
        run = kv_lo <= (iq * block_q + block_q - 1)
    if window > 0:
        run = jnp.logical_and(run, (iq * block_q) - kv_hi < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                # [bkv, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= kv_pos)
        if window > 0:
            mask = jnp.logical_and(mask, q_pos - kv_pos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nkv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _col_to_row(m_ref[...] + jnp.log(l))


def _flash_fwd_pallas(q, k, v, kv_pos, *, scale, causal, window, block_q,
                      block_kv, interpret):
    """q [B,H,S,D]; k,v [B,KV,T,D]; kv_pos [T] or None
    -> (o [B,H,S,D], lse [B,H,S])."""
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, S)
    bkv = min(block_kv, T)
    assert S % bq == 0 and T % bkv == 0, (S, T, bq, bkv)
    nq, nkv = S // bq, T // bkv
    grid = (B, H, nq, nkv)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_kv=bkv, nkv=nkv, has_pos=kv_pos is not None)
    in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bkv, D),
                     lambda b, h, iq, ik: (b, h // G, ik, 0)),
        pl.BlockSpec((1, 1, bkv, D),
                     lambda b, h, iq, ik: (b, h // G, ik, 0)),
    ]
    args = [q, k, v]
    if kv_pos is not None:
        in_specs.append(
            pl.BlockSpec((1, bkv), lambda b, h, iq, ik: (0, ik)))
        args.append(kv_pos.reshape(1, T).astype(jnp.int32))
    # lse is written lane-dense as [B, H, 1, S] rows: a (1, bq) block has
    # the full array's second-minor dim, which Mosaic tiles natively
    out_shape = [
        jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*args)
    return o, lse[:, :, 0]


def _zgrad(x):
    if x is None:
        return None
    return np.zeros(getattr(x, "shape", ()), jax.dtypes.float0)


def _fwd(q, k, v, kv_pos, causal, window, scale, blocks, interpret,
         out_dtype):
    qt = jnp.swapaxes(q, 1, 2)                  # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o, lse = _flash_fwd_pallas(qt, kt, vt, kv_pos, scale=scale,
                               causal=causal, window=window,
                               block_q=blocks[0], block_kv=blocks[1],
                               interpret=interpret)
    o = jnp.swapaxes(o, 1, 2)                   # [B,S,H,D]
    lse_bsh = jnp.transpose(lse, (0, 2, 1))     # [B,S,H]
    return (o, lse_bsh), (q, k, v, o, lse_bsh, kv_pos)


def _bwd_res(res):
    q, k, v, o, lse, kv_pos = res
    return (q, k, v, o, lse, None, None, jnp.int32(0), kv_pos)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_pallas(q, k, v, kv_pos, causal, window, scale, blocks,
                  interpret, out_dtype):
    return _fwd(q, k, v, kv_pos, causal, window, scale, blocks, interpret,
                out_dtype)[0][0]


def _fwd_vjp(q, k, v, kv_pos, causal, window, scale, blocks, interpret,
             out_dtype):
    (o, _), res = _fwd(q, k, v, kv_pos, causal, window, scale, blocks,
                       interpret, out_dtype)
    return o, res


def _bwd_vjp(causal, window, scale, blocks, interpret, out_dtype, res, do):
    # blockwise-recompute backward (ref.py) — the lse layout there is
    # [B, S, H] with H = KV*G ordering identical to ours
    dq, dk, dv, _, _, _, _ = _ref._flash_bwd(
        causal, window, scale, blocks, _bwd_res(res), do)
    return dq, dk, dv, _zgrad(res[5])


_flash_pallas.defvjp(_fwd_vjp, _bwd_vjp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_pallas_lse(q, k, v, kv_pos, causal, window, scale, blocks,
                      interpret, out_dtype):
    return _fwd(q, k, v, kv_pos, causal, window, scale, blocks, interpret,
                out_dtype)[0]


def _fwd_lse_vjp(q, k, v, kv_pos, causal, window, scale, blocks, interpret,
                 out_dtype):
    return _fwd(q, k, v, kv_pos, causal, window, scale, blocks, interpret,
                out_dtype)


def _bwd_lse_vjp(causal, window, scale, blocks, interpret, out_dtype, res,
                 cts):
    do, dlse = cts
    dq, dk, dv, _, _, _, _ = _ref._flash_bwd_core(
        causal, window, scale, blocks, _bwd_res(res), do, dlse)
    return dq, dk, dv, _zgrad(res[5])


_flash_pallas_lse.defvjp(_fwd_lse_vjp, _bwd_lse_vjp)


def _positions(kv_positions, q_offset, T):
    """Fold q_offset into explicit KV positions (masks are
    difference-based, so shifting KV by −q_offset is exact) — this is how
    traced offsets (CP allgather's axis_index) reach the Pallas kernel."""
    static_zero = isinstance(q_offset, (int, np.integer)) and q_offset == 0
    if kv_positions is None:
        if static_zero:
            return None
        return jnp.arange(T, dtype=jnp.int32) - jnp.asarray(
            q_offset, jnp.int32)
    kv_positions = jnp.asarray(kv_positions, jnp.int32)
    if static_zero:
        return kv_positions
    return kv_positions - jnp.asarray(q_offset, jnp.int32)


def fits(S, T, block_q=512, block_kv=512, segmented=False) -> bool:
    """Whether the kernel takes S queries and T keys: each a multiple of
    its block (or shorter than it), and no segment ids."""
    return (not segmented and S % min(block_q, S) == 0
            and T % min(block_kv, T) == 0)


def _blocks(S, T, block_q, block_kv):
    """Static (bq, bkv) for the kernel; raises where a length is not a
    multiple of its block (a caller that wants the jnp path asks
    ``kernels.ops`` for ``impl="ref"``)."""
    bq = min(block_q, S)
    bkv = min(block_kv, T)
    if not fits(S, T, block_q, block_kv):
        raise ValueError(
            f"Pallas flash attention: S={S}, T={T} are not multiples of "
            f"the blocks block_q={bq}, block_kv={bkv}; pad them or use "
            f"impl='ref'")
    return bq, bkv


def flash_attention(q, k, v, *, causal=True, window=0, segment_q=None,
                    segment_kv=None, scale: Optional[float] = None,
                    q_offset=0, kv_positions=None, interpret: bool = False,
                    block_q: int = 512, block_kv: int = 512):
    """Pallas flash attention; q [B,S,H,D], k/v [B,T,KV,D].

    q_offset / kv_positions run natively via the positions operand.  The
    kernel has no segment-id operand: packed sequences raise here and go
    through ``impl="ref"``."""
    if segment_q is not None or segment_kv is not None:
        raise ValueError(
            "Pallas flash attention has no segment-id path; use "
            "impl='ref' for packed sequences")
    B, S, H, D = q.shape
    T = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    blocks = _blocks(S, T, block_q, block_kv)
    kv_pos = _positions(kv_positions, q_offset, T)
    return _flash_pallas(q, k, v, kv_pos, bool(causal), int(window),
                         float(scale), blocks, bool(interpret), q.dtype)


def flash_attention_lse(q, k, v, *, causal=True, window=0,
                        scale: Optional[float] = None, q_offset=0,
                        kv_positions=None, interpret: bool = False,
                        block_q: int = 512, block_kv: int = 512):
    """Pallas flash attention returning ``(o [B,S,H,D], lse [B,S,H])``.

    The VJP consumes the lse cotangent, so partial results over disjoint
    KV chunks merged with :func:`merge_flash_partials` differentiate
    exactly (the overlap-pipelined CP path relies on this)."""
    B, S, H, D = q.shape
    T = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    blocks = _blocks(S, T, block_q, block_kv)
    kv_pos = _positions(kv_positions, q_offset, T)
    return _flash_pallas_lse(q, k, v, kv_pos, bool(causal), int(window),
                             float(scale), blocks, bool(interpret),
                             q.dtype)


def merge_flash_partials(o_parts, lse_parts):
    """Online-softmax merge of flash partials over disjoint KV chunks.

    o_parts [N,B,S,H,D] (or a list of [B,S,H,D]), lse_parts [N,B,S,H] (or
    a list of [B,S,H]) -> (o, lse) over the union of the chunks.  Exact:
    each partial is its chunk's softmax-weighted value sum with its
    log-sum-exp, so reweighting by ``exp(lse_i − lse)`` reconstructs the
    global softmax.  A fully-masked chunk carries lse ≈ −1e30 and merges
    with weight 0, which also zeroes its (meaningless) o part.
    Differentiable: plain jnp, and the chunk kernels' VJPs consume the
    resulting (do_i, dlse_i) cotangents.
    """
    if isinstance(o_parts, (list, tuple)):
        o_parts = jnp.stack(o_parts)
    if isinstance(lse_parts, (list, tuple)):
        lse_parts = jnp.stack(lse_parts)
    m = jnp.max(lse_parts, axis=0)
    w = jnp.exp(lse_parts - m[None])
    l = jnp.maximum(jnp.sum(w, axis=0), 1e-30)
    lse = m + jnp.log(l)
    o = jnp.sum(o_parts.astype(jnp.float32) * (w / l[None])[..., None],
                axis=0)
    return o.astype(o_parts.dtype), lse
