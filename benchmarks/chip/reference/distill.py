"""Plain float32 reference of teacher-to-student distillation: a frozen
teacher's forward pass to its final hidden states, the student's forward
pass, the loss ``(1 - alpha) * CE + alpha * T^2 * KL(p_teacher || p_student)``
with both distributions at temperature T over the shared vocabulary (each
model's output layer is its tied embedding, or its own unembedding), and
AdamW on the student.

The global batch is split into consecutive microbatches of ``mbs``
samples; the step's loss and gradient are the means over microbatches.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common as rc
from . import train as rt
from .mllm import _layer_layout
from .. import gen


def _lm_layout(c: dict) -> dict:
    d, V = c["d_model"], c["vocab_size"]
    out = {"embed": gen.Leaf((V, d), "embed"),
           "final_norm": {"scale": gen.Leaf((d,), "ones")},
           "layers": {"sub0": _layer_layout(
               c["num_layers"], d, c["num_heads"], c["num_kv_heads"],
               c["head_dim"], c["d_ff"])}}
    if not c["tie_embeddings"]:
        out["unembed"] = gen.Leaf((d, V))
    return out


def layout(cfg: dict) -> dict:
    return {"student": _lm_layout(cfg["student"]),
            "teacher": _lm_layout(cfg["teacher"])}


def _hidden(m: rc.Math, c: dict, p, tokens):
    a = dict(heads=c["num_heads"], kv_heads=c["num_kv_heads"],
             head_dim=c["head_dim"], theta=c["rope_theta"],
             eps=c["norm_eps"])
    h = rc.stack(m, p["layers"]["sub0"], p["embed"][tokens], a, causal=True)
    return rc.rms_norm(h, p["final_norm"]["scale"], a["eps"])


def _logits(m: rc.Math, c: dict, p, h):
    if c["tie_embeddings"]:
        return m.ein("bsd,vd->bsv", h, p["embed"])
    return m.ein("bsd,dv->bsv", h, p["unembed"])


def microbatch_loss(m: rc.Math, cfg: dict, ps, pt, mb):
    alpha, T = cfg["distill"]["alpha"], cfg["distill"]["temperature"]
    t, s = cfg["teacher"], cfg["student"]
    zt = _logits(m, t, pt, _hidden(m, t, pt, mb["tokens"]))
    zt = jax.lax.stop_gradient(zt)
    zs = _logits(m, s, ps, _hidden(m, s, ps, mb["tokens"]))
    ce = rc.cross_entropy(zs, mb["labels"], mb["loss_mask"])
    lpt = jax.nn.log_softmax(zt / T, -1)
    kl_tok = jnp.sum(jnp.exp(lpt) * (lpt - jax.nn.log_softmax(zs / T, -1)),
                     -1)
    w = mb["loss_mask"]
    kl = jnp.sum(kl_tok * w) / jnp.maximum(jnp.sum(w), 1.0)
    return (1 - alpha) * ce + alpha * T * T * kl


def train(m: rc.Math, cfg: dict, seed: int, batches, mbs: int, *,
          drop_half: bool = False):
    """Training steps over ``batches`` from the seed's weights (see
    ``mllm.train`` for ``drop_half`` and the readings)."""
    mb_lists = []
    for b in batches:
        n = len(b["tokens"])
        mbl = [{k: np.asarray(b[k])[j:j + mbs] for k in
                ("tokens", "labels", "loss_mask")} for j in range(0, n, mbs)]
        mb_lists.append(mbl[:max(len(mbl) // 2, 1)] if drop_half else mbl)
    return rt.train(lambda ps, fr, mb: microbatch_loss(m, cfg, ps,
                                                       fr["teacher"], mb),
                    layout(cfg), seed, mb_lists, cfg["optimizer"],
                    train_key="student")
