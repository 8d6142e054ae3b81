"""Gradient of a token-embedding lookup as a Pallas TPU kernel.

The transpose of ``jnp.take(table, ids, axis=0)`` adds each cotangent row
into the table row its id names.  XLA lowers that to a scatter-add that
updates one row at a time, in the table's dtype.

Here the ids are sorted once, with their rows, so the rows that land in one
vocabulary tile of ``block_v`` table rows lie in one contiguous range
``[lo_t, hi_t)`` of the sorted rows (``searchsorted`` over the tile
edges).  The kernel's grid runs over (vocabulary tiles × width tiles).  For
each it walks the ``block_n``-row chunks of the sorted rows that overlap
the tile's range, copies each in from HBM (double-buffered), builds the
``[block_v, block_n]`` one-hot of the chunk's ids against the tile's rows,
and adds ``one_hot @ chunk`` into an f32 accumulator on the MXU.  A tile
that no id falls in writes zeros.

Work whatever the ids' distribution: the tiles' ranges are disjoint, so a
chunk is visited once plus once per tile edge inside it, at most
``V / block_v + N / block_n`` visits of ``2 · block_v · block_n · d``
FLOPs, and the ``[V, d]`` table is written once.  The sort, the gather of
the rows into sorted order and the searchsorted run in XLA.

The sum is the scatter-add's, accumulated in f32 and rounded once to the
table's dtype.  Ids outside ``[0, V)`` match no row and add nothing, as
the scatter drops them.

:func:`embed_lookup` is ``jnp.take`` with this backward (``jax.custom_vjp``),
which runs under the scope ``embed_grad``.  It runs per device: a step
jitted over several devices keeps XLA's scatter (``kernels.ops``), since
the TPU compiler cannot partition the kernel; a ``shard_map`` with a sum
over the data axis would lift that, and is not built.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_VMEM_BUDGET = 32 * 2**20


def fits(V: int, d: int, block_v: int = 512) -> bool:
    """Whether the kernel takes a ``[V, d]`` table: V a multiple of the
    vocabulary tile (or shorter than it), d a multiple of 128."""
    return V % min(block_v, V) == 0 and d % LANES == 0


def _check(V: int, d: int, block_v: int) -> None:
    if not fits(V, d, block_v):
        raise ValueError(
            f"embed_grad: a [{V}, {d}] table needs V a multiple of "
            f"block_v={min(block_v, V)} and d a multiple of {LANES}; use "
            "impl='ref'")


def _vmem_bytes(bv, bn, bd, itemsize, out_itemsize):
    """Two row buffers, the f32 accumulator and matmul result, two output
    tiles, and the one-hot with its mask."""
    return (2 * bn * bd * itemsize + 2 * bv * bd * 4
            + 2 * bv * bd * out_itemsize + bv * bn * (4 + itemsize))


def _block_d(bv, bn, d, itemsize, out_itemsize):
    """The widest multiple of 128 dividing d whose working set fits
    ``_VMEM_BUDGET``."""
    for bd in range(d, 0, -LANES):
        if d % bd == 0 and _vmem_bytes(bv, bn, bd, itemsize,
                                       out_itemsize) <= _VMEM_BUDGET:
            return bd
    return LANES


def _kernel(lo_ref, hi_ref, ids_ref, g_hbm, out_ref, buf, sem, acc, *,
            bv, bn, bd, precision):
    t, j = pl.program_id(0), pl.program_id(1)
    lo, hi = lo_ref[t], hi_ref[t]
    c0 = lo // bn
    n = jnp.where(hi > lo, (hi + bn - 1) // bn - c0, 0)

    def copy(i, slot):
        return pltpu.make_async_copy(
            g_hbm.at[pl.ds((c0 + i) * bn, bn), pl.ds(j * bd, bd)],
            buf.at[slot], sem.at[slot])

    @pl.when(n > 0)
    def _first():
        copy(0, 0).start()

    acc[...] = jnp.zeros_like(acc)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bv, bn), 0) + t * bv

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n)
        def _next():
            copy(i + 1, 1 - slot).start()

        copy(i, slot).wait()
        hot = (ids_ref[c0 + i] == rows).astype(jnp.float32).astype(
            buf.dtype)
        acc[...] += jax.lax.dot_general(
            hot, buf[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        return carry

    jax.lax.fori_loop(0, n, body, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def embed_grad(ids, g, vocab: int, dtype, *, block_v: int = 512,
               block_n: int = 512, interpret: bool = False):
    """The ``[vocab, d]`` cotangent of ``jnp.take(table, ids, axis=0)``
    from the ids (any shape) and the cotangent rows ``g`` (``ids.shape +
    (d,)``), in ``dtype``."""
    d = g.shape[-1]
    _check(vocab, d, block_v)
    bv = min(block_v, vocab)
    ids = ids.reshape(-1).astype(jnp.int32)
    g = g.reshape(-1, d)
    N = ids.shape[0]
    bn = min(block_n, -(-N // LANES) * LANES)
    n_pad = -(-N // bn) * bn - N
    ids_s, order = jax.lax.sort((ids, jnp.arange(N, dtype=jnp.int32)),
                                num_keys=1)
    g_s = jnp.take(g, order, axis=0, mode="clip", unique_indices=True)
    if n_pad:
        # padding ids equal vocab: they sort last and match no row
        ids_s = jnp.pad(ids_s, (0, n_pad), constant_values=vocab)
        g_s = jnp.pad(g_s, ((0, n_pad), (0, 0)))
    nt = vocab // bv
    edges = jnp.searchsorted(ids_s, jnp.arange(nt + 1, dtype=jnp.int32) * bv,
                             method="compare_all")
    lo, hi = edges[:-1].astype(jnp.int32), edges[1:].astype(jnp.int32)
    nc = ids_s.shape[0] // bn
    itemsize = jnp.dtype(g.dtype).itemsize
    out_itemsize = jnp.dtype(dtype).itemsize
    bd = _block_d(bv, bn, d, itemsize, out_itemsize)
    vmem = _vmem_bytes(bv, bn, bd, itemsize, out_itemsize) + nc * 8 * bn * 4
    precision = (jax.lax.Precision.HIGHEST if g.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    kernel = functools.partial(_kernel, bv=bv, bn=bn, bd=bd,
                               precision=precision)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, d // bd),
            in_specs=[
                pl.BlockSpec((nc, 1, bn), lambda t, j, lo, hi: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((bv, bd), lambda t, j, lo, hi: (t, j)),
            scratch_shapes=[pltpu.VMEM((2, bn, bd), g.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((bv, bd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((vocab, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(vmem + 8 * 2**20, 16 * 2**20)),
        interpret=interpret,
        name="embed_grad",
    )(lo, hi, ids_s.reshape(nc, 1, bn), g_s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _lookup(table, ids, block_v, interpret):
    return jnp.take(table, ids, axis=0)


def _lookup_fwd(table, ids, block_v, interpret):
    # a [V, 0] array carries the table's rows and dtype, and no bytes
    like = jnp.zeros((table.shape[0], 0), table.dtype)
    return jnp.take(table, ids, axis=0), (ids, like)


def _lookup_bwd(block_v, interpret, res, g):
    ids, like = res
    with jax.named_scope("embed_grad"):
        dt = embed_grad(ids, g, like.shape[0], like.dtype, block_v=block_v,
                        interpret=interpret)
    return dt, None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def embed_lookup(table, ids, *, block_v: int = 512,
                 interpret: bool = False):
    """``jnp.take(table, ids, axis=0)`` whose table gradient is
    :func:`embed_grad`.  Raises ``ValueError`` where :func:`fits` refuses
    the table; a caller that wants XLA's scatter asks ``kernels.ops`` for
    ``impl="ref"``."""
    _check(*table.shape, block_v)
    return _lookup(table, ids, block_v, bool(interpret))
