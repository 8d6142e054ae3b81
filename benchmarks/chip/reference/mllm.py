"""Plain float32 reference of MLLM training: a ViT encoder over image
patches, its 4:1 merge into language-model embeddings, the LM backbone with
those embeddings in place of the image tokens, masked next-token
cross-entropy, and AdamW.

Semantics it holds the program to (one training step):
  * the global batch is split into microbatches of ``mbs`` consecutive
    samples of the wavefront order (``wavefront_order``), which the
    reference works out itself from which samples hold an image;
  * a microbatch's loss is the masked mean cross-entropy of its samples,
    and the step's loss and gradient are the means over microbatches;
  * an image sample's embeddings are ViT(patches) merged ``downsample``:1,
    projected by ``vision_proj``, and replace its tokens at ``image_pos``;
    a text sample's tokens are left as they are;
  * AdamW with global-norm clipping updates every parameter.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import common as rc
from . import train as rt
from .. import gen


def _layer_layout(L, d, h, kv, hd, f):
    return {"norm1": {"scale": gen.Leaf((L, d), "ones")},
            "attn": {"wq": gen.Leaf((L, d, h, hd), fan_in=(1,)),
                     "wk": gen.Leaf((L, d, kv, hd), fan_in=(1,)),
                     "wv": gen.Leaf((L, d, kv, hd), fan_in=(1,)),
                     "wo": gen.Leaf((L, h, hd, d), fan_in=(1, 2))},
            "norm2": {"scale": gen.Leaf((L, d), "ones")},
            "mlp": {"w_gate": gen.Leaf((L, d, f), fan_in=(1,)),
                    "w_up": gen.Leaf((L, d, f), fan_in=(1,)),
                    "w_down": gen.Leaf((L, f, d), fan_in=(1,))}}


def layout(cfg: dict) -> dict:
    """Every parameter of the configuration, as ``gen.Leaf`` shapes."""
    v = cfg["vision_encoder"]
    d, dv = cfg["d_model"], v["d_model"]
    return {
        "vit": {
            "patch_proj": gen.Leaf((v["patch_dim"], dv)),
            "layers": _layer_layout(v["num_layers"], dv, v["num_heads"],
                                    v["num_heads"], dv // v["num_heads"],
                                    v["d_ff"]),
            "final_norm": {"scale": gen.Leaf((dv,), "ones")},
            "merge_proj": gen.Leaf((dv * v["downsample"],
                                    cfg["vision_dim"]))},
        "lm": {
            "embed": gen.Leaf((cfg["vocab_size"], d), "embed"),
            "final_norm": {"scale": gen.Leaf((d,), "ones")},
            "layers": {"sub0": _layer_layout(
                cfg["num_layers"], d, cfg["num_heads"], cfg["num_kv_heads"],
                cfg["head_dim"], cfg["d_ff"])},
            "unembed": gen.Leaf((d, cfg["vocab_size"])),
            "vision_proj": gen.Leaf((cfg["vision_dim"], d))},
    }


def _widths(cfg: dict):
    v = cfg["vision_encoder"]
    lm = dict(heads=cfg["num_heads"], kv_heads=cfg["num_kv_heads"],
              head_dim=cfg["head_dim"], theta=cfg["rope_theta"],
              eps=cfg["norm_eps"])
    vit = dict(heads=v["num_heads"], kv_heads=v["num_heads"],
               head_dim=v["d_model"] // v["num_heads"],
               theta=v["rope_theta"], eps=v["norm_eps"])
    return lm, vit


def microbatch_loss(m: rc.Math, cfg: dict, params, mb):
    """Loss of one microbatch ``mb`` (host arrays of ``mbs`` samples)."""
    a_lm, a_vit = _widths(cfg)
    ds = cfg["vision_encoder"]["downsample"]
    pv, pl = params["vit"], params["lm"]
    n, P, _ = mb["patches"].shape
    x = m.ein("bpe,ed->bpd", mb["patches"], pv["patch_proj"])
    x = rc.stack(m, pv["layers"], x, a_vit, causal=False)
    x = rc.rms_norm(x, pv["final_norm"]["scale"], a_vit["eps"])
    x = x.reshape(n, P // ds, ds * x.shape[-1])
    emb = m.ein("bkm,mv->bkv", x, pv["merge_proj"])
    vh = m.ein("bkv,vd->bkd", emb, pl["vision_proj"])

    h = pl["embed"][mb["tokens"]]
    rows = jnp.arange(n)[:, None]
    keep = h[rows, mb["image_pos"]]
    img = (mb["image_valid"] * mb["has_image"][:, None])[..., None] > 0
    h = h.at[rows, mb["image_pos"]].set(jnp.where(img, vh, keep))
    h = rc.stack(m, pl["layers"]["sub0"], h, a_lm, causal=True)
    h = rc.rms_norm(h, pl["final_norm"]["scale"], a_lm["eps"])
    logits = m.ein("bsd,dv->bsv", h, pl["unembed"])
    return rc.cross_entropy(logits, mb["labels"], mb["loss_mask"])


KEYS = ("tokens", "labels", "loss_mask", "patches", "image_pos",
        "image_valid", "has_image")


def wavefront_order(has_image) -> list:
    """The wavefront order (Algorithm 1) of a batch whose samples differ
    only in whether they hold an image: Algorithm 1 takes the samples by
    rising encoder work and inserts each at the first position of least
    makespan.  An image sample's encoder forward pass overlaps the LM's
    work on the others only from the front, so every image sample goes
    first, and a sample that ties with those before it goes ahead of them:
    image samples, then text samples, each by falling index."""
    idx = range(len(has_image) - 1, -1, -1)
    return ([i for i in idx if has_image[i]] +
            [i for i in idx if not has_image[i]])


def interleaved_order(has_image) -> list:
    """A wrong grouping, for the fault ``pairing``: image and text samples
    alternate, so a microbatch of two pairs one of each."""
    order = wavefront_order(has_image)
    k = int(np.sum(np.asarray(has_image) > 0))
    img, txt = order[:k], order[k:]
    out = [s for pair in zip(img, txt) for s in pair]
    return out + img[len(txt):] + txt[len(img):]


def microbatches(batch: dict, order, mbs: int):
    """The batch's microbatches of ``mbs`` consecutive samples of
    ``order``."""
    order = np.asarray(order)
    for j in range(len(order) // mbs):
        rows = order[j * mbs:(j + 1) * mbs]
        yield {k: np.asarray(batch[k])[rows].astype(
            np.float32 if k == "patches" else batch[k].dtype) for k in KEYS}


def train(m: rc.Math, cfg: dict, seed: int, batches, mbs: int, *,
          fault: str | None = None):
    """Three (``len(batches)``) training steps from the seed's weights.
    ``fault`` plants one: ``half_batch`` trains every step on the first
    half of its microbatches only, ``pairing`` groups the samples by
    ``interleaved_order``.  Returns the readings the benchmark compares."""
    rule = interleaved_order if fault == "pairing" else wavefront_order
    mb_lists = [list(microbatches(b, rule(b["has_image"]), mbs))
                for b in batches]
    if fault == "half_batch":
        mb_lists = [mbl[:max(len(mbl) // 2, 1)] for mbl in mb_lists]
    return rt.train(lambda p, _, mb: microbatch_loss(m, cfg, p, mb),
                    layout(cfg), seed, mb_lists, cfg["optimizer"])
