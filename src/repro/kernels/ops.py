"""Dispatching wrappers around the Pallas kernels.

Each op picks an implementation:

* ``pallas``           — the TPU kernel (``pl.pallas_call``).
* ``pallas_interpret`` — same kernel body, interpret mode (CPU correctness).
* ``ref``              — the memory-efficient jnp path (``ref.py``).
* ``auto``             — pallas on TPU, ref elsewhere.

Where the kernel cannot take a call's shapes (a length that is not a
multiple of its block, or segment ids), a Pallas tier that ``auto`` or
``REPRO_KERNEL_IMPL`` picked runs ``ref`` instead and warns once per shape;
a Pallas tier the caller named raises ``ValueError`` in the kernel.

The model stack always calls through here, so swapping in the TPU kernel is a
config change, not a code change.  A ``REPRO_KERNEL_IMPL`` environment
variable overrides every dispatch repo-wide (CI forces
``pallas_interpret`` through the full driver stack with it).
"""
from __future__ import annotations

import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref

_IMPLS = ("auto", "pallas", "pallas_interpret", "ref", "ref_naive")


def _on_tpu() -> bool:
    # deliberately uncached: backend selection can change mid-process
    # (tests flip platforms; jax.default_backend is already memoized
    # per-config internally)
    try:
        return jax.default_backend() == "tpu"
    except Exception:          # pragma: no cover
        return False


_PALLAS = ("pallas", "pallas_interpret")
_REF_WARNED: set = set()


def resolve(impl: str, *, fits: bool = True, what: str = "") -> str:
    """The tier a call runs.  ``fits`` says whether the Pallas kernel
    takes the call's shapes (``what`` names them in the warning)."""
    asked = impl
    env = os.environ.get("REPRO_KERNEL_IMPL", "")
    if env:
        if env not in _IMPLS:
            raise ValueError(
                f"REPRO_KERNEL_IMPL={env!r}: expected one of {_IMPLS}")
        impl = env
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl not in _IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}")
    if impl in _PALLAS and not fits and asked not in _PALLAS:
        if what not in _REF_WARNED:
            _REF_WARNED.add(what)
            warnings.warn(f"{what}: the Pallas kernel cannot take these "
                          "shapes; running impl='ref'", stacklevel=3)
        return "ref"
    return impl


def flash_impl(impl: str, S: int, T: int, *, segmented: bool = False,
               block_q: int = 512, block_kv: int = 512) -> str:
    """:func:`resolve` for flash attention over S queries and T keys."""
    from repro.kernels import flash_attention as fa
    return resolve(impl, fits=fa.fits(S, T, block_q, block_kv, segmented),
                   what=f"flash attention S={S} T={T}"
                   + (" with segment ids" if segmented else ""))


# --------------------------------------------------------------------------- #
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    segment_q=None, segment_kv=None,
                    scale: Optional[float] = None, q_offset=0,
                    kv_positions=None, impl: str = "auto",
                    block_q: int = 512, block_kv: int = 512):
    impl = flash_impl(impl, q.shape[1], k.shape[1],
                      segmented=segment_q is not None
                      or segment_kv is not None,
                      block_q=block_q, block_kv=block_kv)
    if impl in _PALLAS:
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(
            q, k, v, causal=causal, window=window,
            segment_q=segment_q, segment_kv=segment_kv, scale=scale,
            q_offset=q_offset, kv_positions=kv_positions,
            interpret=(impl == "pallas_interpret"),
            block_q=block_q, block_kv=block_kv)
    if impl == "ref":
        return ref.flash_attention_jnp(
            q, k, v, causal=causal, window=window,
            segment_q=segment_q, segment_kv=segment_kv, scale=scale,
            q_offset=q_offset, kv_positions=kv_positions,
            block_q=block_q, block_kv=block_kv)
    if impl == "ref_naive":
        return ref.mha_reference(
            q, k, v, causal=causal, window=window,
            segment_q=segment_q, segment_kv=segment_kv, scale=scale,
            q_offset=q_offset, kv_positions=kv_positions)
    raise ValueError(f"unknown attention impl {impl!r}")


# --------------------------------------------------------------------------- #
def flash_attention_lse(q, k, v, *, causal: bool = True, window: int = 0,
                        scale: Optional[float] = None, q_offset=0,
                        kv_positions=None, impl: str = "auto",
                        block_q: int = 512, block_kv: int = 512):
    """Flash attention returning ``(o, lse)`` with a merge-aware VJP.

    The chunked CP path calls this per KV chunk and merges the partials
    with ``flash_attention.merge_flash_partials``; no ``ref_naive`` tier
    (the naive oracle has no lse output)."""
    impl = flash_impl(impl, q.shape[1], k.shape[1], block_q=block_q,
                      block_kv=block_kv)
    if impl in _PALLAS:
        from repro.kernels import flash_attention as fa
        return fa.flash_attention_lse(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset, kv_positions=kv_positions,
            interpret=(impl == "pallas_interpret"),
            block_q=block_q, block_kv=block_kv)
    if impl == "ref":
        return ref.flash_attention_jnp_lse(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset, kv_positions=kv_positions,
            block_q=block_q, block_kv=block_kv)
    raise ValueError(f"attention impl {impl!r} has no lse-returning form")


# --------------------------------------------------------------------------- #
def distill_kl(h_student, w_student, h_teacher, w_teacher, *, mask=None,
               temperature: float = 1.0, impl: str = "auto",
               block_v: int = 2048):
    """Chunked-vocab KL(p_t || p_s) from hidden states (never materializes
    the [N, V] teacher logits — the kernel form of Maestro's §3.1 colocation
    insight)."""
    from repro.kernels import distill_kl_pallas as dkp
    N, V = h_student.shape[0], w_student.shape[1]
    impl = resolve(impl, fits=dkp.fits(N, V, block_v=block_v),
                   what=f"distill_kl N={N} V={V}")
    if impl in _PALLAS:
        from repro.kernels import distill_kl as dk
        return dk.distill_kl(h_student, w_student, h_teacher, w_teacher,
                             mask=mask, temperature=temperature,
                             interpret=(impl == "pallas_interpret"),
                             block_v=block_v)
    if impl == "ref":
        from repro.kernels import distill_kl as dk
        return dk.distill_kl_chunked_jnp(
            h_student, w_student, h_teacher, w_teacher, mask=mask,
            temperature=temperature, block_v=block_v)
    if impl == "ref_naive":
        return ref.distill_kl_reference(h_student, w_student, h_teacher,
                                        w_teacher, mask=mask,
                                        temperature=temperature)
    raise ValueError(f"unknown distill_kl impl {impl!r}")


# --------------------------------------------------------------------------- #
def embed_impl(impl: str, V: int, d: int, *, block_v: int = 512) -> str:
    """:func:`resolve` for the gradient of a ``[V, d]`` table's lookup.
    Under a kernel layout over several devices the lookup keeps XLA's
    scatter (``ref``): the kernel is not partitioned over a mesh."""
    from repro.kernels import embed_grad as eg
    from repro.models import common as cm
    layout = cm._KERNEL_LAYOUT
    if layout is not None and layout.mesh.size > 1:
        return "ref"
    return resolve(impl, fits=eg.fits(V, d, block_v),
                   what=f"embed_grad V={V} d={d}")


def embed_lookup(table, ids, *, impl: str = "auto", block_v: int = 512):
    """``jnp.take(table, ids, axis=0)``.  The Pallas tiers differentiate
    it with the sorted one-hot contraction of ``embed_grad.py``; ``ref``
    with XLA's scatter-add."""
    V, d = table.shape
    impl = embed_impl(impl, V, d, block_v=block_v)
    if impl in _PALLAS:
        from repro.kernels import embed_grad as eg
        return eg.embed_lookup(table, ids, block_v=block_v,
                               interpret=(impl == "pallas_interpret"))
    if impl in ("ref", "ref_naive"):
        return jnp.take(table, ids, axis=0)
    raise ValueError(f"unknown embed_lookup impl {impl!r}")


# --------------------------------------------------------------------------- #
def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128, impl: str = "auto"):
    """Mamba2 SSD over a full sequence. See ref.ssd_reference for shapes."""
    impl = resolve(impl)
    if impl in _PALLAS:
        from repro.kernels import ssd_scan as ssd
        return ssd.ssd_scan(x, dt, A, B, C, D, chunk=chunk,
                            interpret=(impl == "pallas_interpret"))
    if impl == "ref":
        from repro.kernels import ssd_scan as ssd
        return ssd.ssd_chunked_jnp(x, dt, A, B, C, D, chunk=chunk)
    if impl == "ref_naive":
        return ref.ssd_reference(x, dt, A, B, C, D)
    raise ValueError(f"unknown ssd impl {impl!r}")
