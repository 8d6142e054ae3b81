"""Decoder-only LM covering the dense / MoE / SSM / hybrid families.

Layers are *grouped* for scan-over-layers: a group is a (possibly
heterogeneous) period of sub-layers whose parameters are stacked over the
number of repeats.  Dense/MoE/SSM models have one group with period 1; jamba
has period ``attn_period`` (8) with mamba/attention mixers and MLP/MoE FFNs
interleaved.  Each sub-layer body is wrapped in ``jax.checkpoint``
(activation remat) — compile time and HBM stay bounded at 500K-token shapes.

VLM archs (``cfg.vision_dim > 0``) additionally scatter projected patch
embeddings (delivered by the stubbed frontend) into the token stream.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.types import ArchConfig
from repro.kernels import ops as kops
from repro.models import attention as att
from repro.models import common as cm
from repro.models import mamba as mb
from repro.models import mlp as mlpm
from repro.models import moe as moem
from repro.models.common import ParamSpec


# --------------------------------------------------------------------------- #
# Norm helpers
# --------------------------------------------------------------------------- #
def norm_specs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    if cfg.norm_type == "ln":
        return {"scale": ParamSpec((d,), ("embed_nosplit",), "ones"),
                "bias": ParamSpec((d,), ("embed_nosplit",), "zeros")}
    return {"scale": ParamSpec((d,), ("embed_nosplit",), "ones")}


def apply_norm(p, x, cfg: ArchConfig):
    if cfg.norm_type == "ln":
        return cm.layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return cm.rms_norm(x, p["scale"], cfg.norm_eps)


# --------------------------------------------------------------------------- #
# Layer-kind layout
# --------------------------------------------------------------------------- #
def layer_kinds(cfg: ArchConfig) -> list:
    """Per layer: (mixer, ffn) with mixer ∈ {attn, mamba}, ffn ∈ {mlp, moe,
    None}."""
    kinds = []
    for i in range(cfg.num_layers):
        mixer = "attn" if cfg.is_attn_layer(i) else "mamba"
        if cfg.is_moe_layer(i):
            ffn = "moe"
        elif cfg.d_ff > 0:
            ffn = "mlp"
        else:
            ffn = None
        kinds.append((mixer, ffn))
    return kinds


def group_layout(cfg: ArchConfig) -> Tuple[list, int]:
    """Returns (period_kinds, repeats). The whole stack is `repeats` copies
    of `period_kinds` (scan-over-layers granularity)."""
    kinds = layer_kinds(cfg)
    period = cfg.attn_period if cfg.attn_period else 1
    if cfg.moe_period:
        import math
        period = math.lcm(period, cfg.moe_period)
    assert cfg.num_layers % period == 0, (cfg.name, period)
    reps = cfg.num_layers // period
    pk = kinds[:period]
    for r in range(reps):
        assert kinds[r * period:(r + 1) * period] == pk
    return pk, reps


def _sublayer_specs(cfg: ArchConfig, mixer: str, ffn: Optional[str]) -> dict:
    s: dict = {"norm1": norm_specs(cfg)}
    if mixer == "attn":
        s["attn"] = att.attn_specs(cfg)
    else:
        s["mamba"] = mb.mamba_specs(cfg)
    if ffn is not None:
        s["norm2"] = norm_specs(cfg)
        s[ffn] = mlpm.mlp_specs(cfg) if ffn == "mlp" else moem.moe_specs(cfg)
    return s


def lm_specs(cfg: ArchConfig) -> dict:
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    pk, reps = group_layout(cfg)
    period = {}
    for j, (mixer, ffn) in enumerate(pk):
        period[f"sub{j}"] = _sublayer_specs(cfg, mixer, ffn)
    specs = {
        "embed": ParamSpec((cfg.padded_vocab, cfg.d_model),
                           ("vocab", "embed"), "embed", dt),
        "final_norm": norm_specs(cfg),
        "layers": cm.stack_specs(period, reps),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((cfg.d_model, cfg.padded_vocab),
                                     ("embed", "vocab"), "normal", dt, (0,))
    if cfg.vision_dim:
        specs["vision_proj"] = ParamSpec((cfg.vision_dim, cfg.d_model),
                                         ("vision", "embed"), "normal",
                                         dt, (0,))
    return specs


# --------------------------------------------------------------------------- #
# Forward passes
# --------------------------------------------------------------------------- #
def _sublayer_fwd(lp, x, cfg: ArchConfig, mixer: str, ffn: Optional[str],
                  *, causal: bool, segment_ids, impl: str,
                  collect_stats: bool = False,
                  tp_axis: Optional[str] = None, tp_attn: bool = False,
                  tp_ffn: bool = False):
    """One (mixer, ffn) sub-layer.  Returns (x, aux); with
    ``collect_stats`` (MoE sub-layers only) returns (x, aux, stats) where
    stats are the [2, E] router statistics of :func:`repro.models.moe.moe`
    — the linear quantities PP microbatch accumulation needs for an exact
    aux term.

    tp_axis/tp_attn/tp_ffn — Megatron-style manual tensor parallelism for
    callers inside a shard_map (``repro.dist.pipeline``): the caller's
    in_specs slice ``heads``/``kv_heads`` (tp_attn) and the FFN ``mlp``
    dim (tp_ffn) over ``tp_axis``, so each shard computes a head/f-slice
    and the output contractions are *partial* sums — psummed here, after
    the mixer and after the FFN.  Everything between the two psums is
    elementwise per slice, so numerics match the unsharded layer exactly
    (the GELU output bias, added after the f-contraction, is pre-scaled by
    1/tp so the psum reconstructs it once)."""
    h = apply_norm(lp["norm1"], x, cfg)
    if mixer == "attn":
        h = att.attention(lp["attn"], h, cfg, causal=causal,
                          segment_ids=segment_ids, impl=impl)
        if tp_attn and tp_axis is not None:
            h = jax.lax.psum(h, tp_axis)
    else:
        h = mb.mamba(lp["mamba"], h, cfg, impl=impl)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    stats = None
    if ffn is not None:
        h = apply_norm(lp["norm2"], x, cfg)
        if ffn == "mlp":
            fp = lp[ffn]
            if tp_ffn and tp_axis is not None and cfg.mlp_act == "gelu":
                tp = jax.lax.psum(1, tp_axis)   # static axis size
                fp = dict(fp, b_out=fp["b_out"] / tp)
            h = mlpm.mlp(fp, h, cfg)
        elif collect_stats:
            h, aux, stats = moem.moe(lp[ffn], h, cfg, return_stats=True)
        else:
            h, aux = moem.moe(lp[ffn], h, cfg)
        if tp_ffn and tp_axis is not None:
            # dense MLP f-slice / per-expert f-slice → partial output.
            # MoE router stats/aux come from the replicated router and are
            # already identical on every tp shard — only h is partial.
            h = jax.lax.psum(h, tp_axis)
        x = x + h
    if collect_stats:
        assert ffn == "moe", "collect_stats only applies to MoE sub-layers"
        return x, aux, stats
    return x, aux


def vision_scatter(p, cfg: ArchConfig, x: jnp.ndarray,
                   batch: dict) -> jnp.ndarray:
    """Scatter projected patch embeddings into the token stream (VLM archs).
    Separated from the vocab lookup so vocab-parallel callers can run it
    once on the combined (post-psum) embedding."""
    if not (cfg.vision_dim and "image_embeds" in batch):
        return x
    vh = jnp.einsum("bkv,vd->bkd",
                    batch["image_embeds"].astype(x.dtype),
                    p["vision_proj"])
    valid = batch["image_valid"].astype(x.dtype)[..., None]   # [B,K,1]
    B = x.shape[0]
    b_ix = jnp.broadcast_to(jnp.arange(B)[:, None],
                            batch["image_pos"].shape)
    upd = vh * valid
    # replace token embedding at image positions (invalid slots add 0 at
    # position 0 after being zeroed and re-added — use where-style update)
    cur = x[b_ix, batch["image_pos"]]
    x = x.at[b_ix, batch["image_pos"]].add(upd - cur * valid)
    return x


def embed_tokens(p, cfg: ArchConfig, batch: dict, *,
                 impl: str = "auto") -> jnp.ndarray:
    x = kops.embed_lookup(p["embed"], batch["tokens"], impl=impl)
    return vision_scatter(p, cfg, x, batch)


def lm_forward(p, cfg: ArchConfig, batch: dict, *, causal: bool = True,
               impl: str = "auto", remat: bool = True,
               logits_out: bool = True):
    """Full-sequence forward. Returns (logits_or_hidden, aux_loss)."""
    pk, reps = group_layout(cfg)
    x = cm.shard_act(embed_tokens(p, cfg, batch, impl=impl), "hidden")
    segment_ids = batch.get("segment_ids")

    def period_body(x, period_params):
        aux_tot = jnp.zeros((), jnp.float32)
        for j, (mixer, ffn) in enumerate(pk):
            fn = functools.partial(_sublayer_fwd, cfg=cfg, mixer=mixer,
                                   ffn=ffn, causal=causal,
                                   segment_ids=segment_ids, impl=impl)
            if remat:
                fn = jax.checkpoint(fn)
            x, aux = fn(period_params[f"sub{j}"], x)
            x = cm.shard_act(x, "hidden")
            aux_tot = aux_tot + aux
        return x, aux_tot

    x, auxs = jax.lax.scan(period_body, x, p["layers"])
    x = apply_norm(p["final_norm"], x, cfg)
    aux = jnp.sum(auxs)
    if not logits_out:
        return x, aux
    logits = unembed(p, cfg, x)
    return logits, aux


def unembed(p, cfg: ArchConfig, x):
    x = cm.grad_dtype_barrier(x)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["unembed"])
    if cfg.vocab_pad:
        # mask padded vocab slots: exact lse/softmax of the unpadded model
        valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(valid, logits, -1e30)
    return cm.shard_act(logits, "logits")


def lm_loss(p, cfg: ArchConfig, batch: dict, *, impl: str = "auto",
            remat: bool = True, aux_weight: float = 0.01):
    logits, aux = lm_forward(p, cfg, batch, impl=impl, remat=remat)
    loss = cm.cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


# --------------------------------------------------------------------------- #
# Prefill / decode (serving)
# --------------------------------------------------------------------------- #
def kv_cache_len(cfg: ArchConfig, total_len: int) -> int:
    if cfg.sliding_window > 0:
        return min(cfg.sliding_window, total_len)
    return total_len


def _sublayer_prefill(lp, x, cfg, mixer, ffn, *, cache_len, impl):
    h = apply_norm(lp["norm1"], x, cfg)
    if mixer == "attn":
        h, cache = att.attention_prefill(lp["attn"], h, cfg,
                                         cache_len=cache_len, impl=impl)
    else:
        h, cache = mb.mamba(lp["mamba"], h, cfg, return_cache=True,
                            impl=impl)
    x = x + h
    if ffn is not None:
        h = apply_norm(lp["norm2"], x, cfg)
        if ffn == "mlp":
            h = mlpm.mlp(lp[ffn], h, cfg)
        else:
            h, _ = moem.moe(lp[ffn], h, cfg)
        x = x + h
    return x, cache


def lm_prefill(p, cfg: ArchConfig, batch: dict, *, impl: str = "auto",
               remat: bool = True, extra_cache: int = 0):
    """Prompt processing. Returns (last-token logits [B,V], cache).

    extra_cache: additional cache capacity beyond the prompt (for decoding
    further tokens)."""
    pk, reps = group_layout(cfg)
    S = batch["tokens"].shape[1]
    clen = kv_cache_len(cfg, S + extra_cache)
    x = embed_tokens(p, cfg, batch)

    def period_body(x, period_params):
        caches = {}
        for j, (mixer, ffn) in enumerate(pk):
            fn = functools.partial(_sublayer_prefill, cfg=cfg, mixer=mixer,
                                   ffn=ffn, cache_len=clen, impl=impl)
            if remat:
                fn = jax.checkpoint(fn)
            x, cache = fn(period_params[f"sub{j}"], x)
            caches[f"sub{j}"] = cache
        return x, caches

    x, caches = jax.lax.scan(period_body, x, p["layers"])
    x = apply_norm(p["final_norm"], x, cfg)
    logits = unembed(p, cfg, x[:, -1:])[:, 0]
    return logits, caches


def _sublayer_decode(lp, x, cache, cfg, mixer, ffn, *, pos):
    h = apply_norm(lp["norm1"], x, cfg)
    if mixer == "attn":
        h, new_cache = att.attention_decode(lp["attn"], h, cache, cfg,
                                            pos=pos)
    else:
        h, new_cache = mb.mamba_decode(lp["mamba"], h, cache, cfg)
    x = x + h
    if ffn is not None:
        h = apply_norm(lp["norm2"], x, cfg)
        if ffn == "mlp":
            h = mlpm.mlp(lp[ffn], h, cfg)
        else:
            h, _ = moem.moe(lp[ffn], h, cfg)
        x = x + h
    return x, new_cache


def lm_decode(p, cfg: ArchConfig, cache, token, pos):
    """One decode step. token [B,1] int32; pos scalar int32 (absolute).
    Returns (logits [B,V], new_cache)."""
    pk, reps = group_layout(cfg)
    x = jnp.take(p["embed"], token, axis=0)

    def period_body(x, inp):
        period_params, period_cache = inp
        new_caches = {}
        for j, (mixer, ffn) in enumerate(pk):
            x, nc = _sublayer_decode(period_params[f"sub{j}"], x,
                                     period_cache[f"sub{j}"], cfg, *pk[j],
                                     pos=pos)
            new_caches[f"sub{j}"] = nc
        return x, new_caches

    x, new_cache = jax.lax.scan(period_body, x, (p["layers"], cache))
    x = apply_norm(p["final_norm"], x, cfg)
    logits = unembed(p, cfg, x)[:, 0]
    return logits, new_cache
