"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid: (batch, heads, chunks) with chunks innermost (sequential on TPU);
the inter-chunk SSM state [headdim, d_state] persists in VMEM scratch
across chunk steps — the recurrence never round-trips HBM, which is the
TPU-native replacement for the paper's warp-level chunk scan.

The jit wrapper folds A into the per-chunk cumulative log-decay
``cum = cumsum(A·dt)`` (laid out as lane-dense [1, Q] rows) and the input
into ``dx = dt⊙x``, so the kernel needs no per-head scalars.  Per chunk
(Q = chunk length), everything is MXU-shaped:
  cb     = C·Bᵀ                      [Q, Q]
  scores = cb ⊙ tril(exp(cum_i−cum_j))
  y      = scores·dx + exp(cum)·(C·stateᵀ)
  state  = exp(cum_Q)·state + (decay_end⊙dx)ᵀ·B

The D·x skip runs in the jit wrapper (fused by XLA).
Backward: custom VJP that recomputes through the chunked-jnp
implementation (same math, memory-bounded).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ssd_scan as _ssd
from repro.kernels.lanes import row_to_col as _row_to_col


def _kernel(dx_ref, cum_ref, B_ref, C_ref, y_ref, state_ref):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    dx = dx_ref[0, 0, 0]                            # [Q, P]  dt·x
    cum_row = cum_ref[0, 0, 0]                      # [1, Q]  log-decays
    cum = _row_to_col(cum_row)                      # [Q, 1]
    Bm = B_ref[0, 0].astype(jnp.float32)            # [Q, N]
    Cm = C_ref[0, 0].astype(jnp.float32)            # [Q, N]
    Q = dx.shape[0]

    iq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(iq >= jq, jnp.exp(cum - cum_row), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = cb * L                                 # [Q, Q]
    y_intra = jax.lax.dot_general(scores, dx, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state = state_ref[...]                          # [P, N]
    y_inter = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum)                # [Q, P]
    y_ref[0, 0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    cum_end = cum_row[:, Q - 1:]                    # [1, 1]
    wx = jnp.exp(cum_end - cum) * dx                # [Q, P]
    new_state = state * jnp.exp(cum_end) + jax.lax.dot_general(
        wx, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [P, N]
    state_ref[...] = new_state


def _ssd_fwd_pallas(x, dt, A, B, C, *, chunk, interpret):
    """x [b,s,h,p], dt [b,s,h] (softplus'ed), A [h], B/C [b,s,n] -> y
    (without the D·x skip)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    Q = min(chunk, s)
    while s % Q:
        Q //= 2
    nc = s // Q
    dtf = dt.astype(jnp.float32)
    dx = (dtf[..., None] * x.astype(jnp.float32))
    dx = dx.transpose(0, 2, 1, 3).reshape(b, h, nc, Q, p)
    a = (dtf * A.astype(jnp.float32)[None, None, :]).transpose(0, 2, 1)
    cum = jnp.cumsum(a.reshape(b, h, nc, Q), axis=-1).reshape(
        b, h, nc, 1, Q)
    Br = B.reshape(b, nc, Q, n)
    Cr = C.reshape(b, nc, Q, n)
    y = pl.pallas_call(
        _kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, p), lambda ib, ih, ic: (ib, ih, ic,
                                                              0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda ib, ih, ic: (ib, ih, ic,
                                                              0, 0)),
            pl.BlockSpec((1, 1, Q, n), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, Q, n), lambda ib, ih, ic: (ib, ic, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Q, p),
                               lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nc, Q, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan_fwd",
    )(dx, cum, Br, Cr)
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd_p(x, dt, A, B, C, D, chunk, interpret):
    y = _ssd_fwd_pallas(x, dt, A, B, C, chunk=chunk, interpret=interpret)
    return (y.astype(jnp.float32)
            + x.astype(jnp.float32) * D[None, None, :, None]).astype(x.dtype)


def _fwd(x, dt, A, B, C, D, chunk, interpret):
    return _ssd_p(x, dt, A, B, C, D, chunk, interpret), (x, dt, A, B, C, D)


def _bwd(chunk, interpret, res, g):
    x, dt, A, B, C, D = res
    _, vjp = jax.vjp(
        lambda *args: _ssd.ssd_chunked_jnp(*args, chunk=chunk), x, dt, A,
        B, C, D)
    return vjp(g)


_ssd_p.defvjp(_fwd, _bwd)


def ssd_scan_pallas(x, dt, A, B, C, D, *, chunk: int = 128,
                    interpret: bool = False):
    return _ssd_p(x, dt, A, B, C, D, int(chunk), bool(interpret))
