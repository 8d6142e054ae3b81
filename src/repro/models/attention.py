"""GQA attention with RoPE, optional QKV bias, sliding window, KV cache.

Three entry points:

* ``attention``         — full-sequence (train / prefill); flash path.
* ``attention_prefill`` — full-sequence + writes the KV cache.
* ``attention_decode``  — one new token against a (possibly rolling) cache.

Cache layout (per layer): ``{"k": [B, C, KV, hd], "v": [B, C, KV, hd]}``
where C = cache capacity (= seq_len, or sliding_window for SWA archs).
Keys are stored post-RoPE.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.types import ArchConfig
from repro.kernels import ops as kops
from repro.models import common as cm
from repro.models.common import ParamSpec


# --------------------------------------------------------------------------- #
# Pluggable full-sequence attention implementation (set by the step builder
# under a mesh context, like common.act_hook).  This is how distributed
# attention — repro.dist.context.cp_attention for CP sections — reaches
# inside every model's self-attention without the models knowing about
# meshes.  The callable contract:
#     impl(q, k, v, *, causal, window, segment_q, segment_kv, scale) -> o
# with q [B, S, H, D] (head-padded), k/v [B, S, KV, D], o like q.
# --------------------------------------------------------------------------- #
_ATTN_IMPL = None


@contextlib.contextmanager
def attention_impl(fn):
    """Install ``fn`` as the full-sequence attention implementation for the
    duration of the context (trace-time; serving paths are unaffected)."""
    global _ATTN_IMPL
    prev = _ATTN_IMPL
    _ATTN_IMPL = fn
    try:
        yield
    finally:
        _ATTN_IMPL = prev


def attn_specs(cfg: ArchConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    specs = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim"),
                        "normal", dt, (0,)),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"),
                        "normal", dt, (0,)),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"),
                        "normal", dt, (0,)),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"),
                        "normal", dt, (0, 1)),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), "zeros", dt)
        specs["bk"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), "zeros", dt)
        specs["bv"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), "zeros", dt)
    return specs


def _project_qkv(p, x, cfg: ArchConfig, positions, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if rope:
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _pad_q_heads(q, cfg: ArchConfig):
    """Append cfg.head_pad zero Q-heads, preserving KV-group layout.

    The zero heads make (H + pad) divide the TP axis so attention compute
    shards cleanly; they are sliced off again before the output projection
    — numerics are unchanged (verified in tests)."""
    if not cfg.head_pad:
        return q
    B, S, H, D = q.shape
    KV = cfg.num_kv_heads
    G = H // KV
    Gp = (H + cfg.head_pad) // KV
    qg = q.reshape(B, S, KV, G, D)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, Gp - G), (0, 0)))
    return qg.reshape(B, S, KV * Gp, D)


def _unpad_o_heads(o, cfg: ArchConfig, H: int):
    if not cfg.head_pad:
        return o
    B, S, Hp, D = o.shape
    KV = cfg.num_kv_heads
    G = H // KV
    og = o.reshape(B, S, KV, Hp // KV, D)[:, :, :, :G]
    return og.reshape(B, S, H, D)


def attention(p, x, cfg: ArchConfig, *, causal: bool = True,
              positions: Optional[jnp.ndarray] = None,
              segment_ids: Optional[jnp.ndarray] = None,
              kv_override=None, rope: bool = True,
              impl: str = "auto") -> jnp.ndarray:
    """Full-sequence attention. x: [B, S, D]."""
    B, S, _ = x.shape
    H = cfg.num_heads
    if positions is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions, rope)
    if kv_override is not None:                       # cross-attention
        k, v = kv_override
    q = cm.shard_act(_pad_q_heads(q, cfg), "attn_q")
    if _ATTN_IMPL is not None:
        o = _ATTN_IMPL(q, k, v, causal=causal, window=cfg.sliding_window,
                       segment_q=segment_ids, segment_kv=segment_ids,
                       scale=None)
    else:
        def kernel(q, k, v):
            return kops.flash_attention(
                q, k, v, causal=causal, window=cfg.sliding_window,
                segment_q=segment_ids, segment_kv=segment_ids, impl=impl)
        tier = kops.flash_impl(impl, q.shape[1], k.shape[1],
                               segmented=segment_ids is not None)
        o = (cm.per_shard(kernel, q, k, v) if tier in ("pallas",
                                                       "pallas_interpret")
             else kernel(q, k, v))
    o = _unpad_o_heads(cm.shard_act(o, "attn_q"), cfg, H)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def attention_prefill(p, x, cfg: ArchConfig, *, cache_len: int,
                      impl: str = "auto"):
    """Causal attention over the prompt; returns (out, cache).

    cache_len — cache capacity.  For SWA archs this may be < S: the cache
    keeps only the trailing ``cache_len`` positions (rolling layout: slot =
    pos % cache_len).
    """
    B, S, _ = x.shape
    H = cfg.num_heads
    positions = jnp.arange(S)[None, :].astype(jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions, rope=True)
    q = cm.shard_act(_pad_q_heads(q, cfg), "attn_q")
    o = kops.flash_attention(q, k, v, causal=True,
                             window=cfg.sliding_window, impl=impl)
    o = _unpad_o_heads(cm.shard_act(o, "attn_q"), cfg, H)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if cache_len >= S:
        pad = cache_len - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        # rolling window: keep the last cache_len keys at slot pos % cache_len
        tail_k = k[:, S - cache_len:]
        tail_v = v[:, S - cache_len:]
        shift = S % cache_len
        kc = jnp.roll(tail_k, shift, axis=1)
        vc = jnp.roll(tail_v, shift, axis=1)
    return out, {"k": kc, "v": vc}


def attention_decode(p, x, cache, cfg: ArchConfig, *, pos: jnp.ndarray,
                     kv_override=None):
    """One-token decode. x: [B, 1, D]; pos: scalar int32 absolute position."""
    B = x.shape[0]
    C = cache["k"].shape[1] if cache is not None else 0
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions, rope=kv_override is None)
    if kv_override is not None:                       # cross-attn: static cache
        kc, vc = kv_override
        return _attend_full(q, kc, vc, p, cfg, valid=None)
    slot = jnp.mod(pos, C)
    kc = cache["k"].at[:, slot].set(k[:, 0].astype(cache["k"].dtype))
    vc = cache["v"].at[:, slot].set(v[:, 0].astype(cache["v"].dtype))
    # absolute position of each slot s: pos - ((pos - s) mod C); valid if >= 0
    slots = jnp.arange(C)
    abs_pos = pos - jnp.mod(pos - slots, C)
    valid = abs_pos >= 0
    if cfg.sliding_window > 0:
        valid &= (pos - abs_pos) < cfg.sliding_window
    out = _attend_full(q, kc, vc, p, cfg, valid=valid)
    return out, {"k": kc, "v": vc}


def _attend_full(q, kc, vc, p, cfg, valid):
    """Direct (non-flash) attention of a single query over a full cache."""
    B, S, H, D = q.shape
    KV = kc.shape[2]
    G = H // KV
    qr = q.reshape(B, S, KV, G, D).astype(jnp.float32)
    logits = jnp.einsum("bskgd,btkd->bkgst", qr,
                        kc.astype(jnp.float32)) * (D ** -0.5)
    if valid is not None:
        logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
    prob = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", prob, vc.astype(jnp.float32))
    o = o.reshape(B, S, H, D).astype(q.dtype)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])
