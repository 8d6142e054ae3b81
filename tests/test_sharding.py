"""Sharding rules: logical-axis mapping, divisibility fallbacks, ZeRO
extension, cache specs — checked against AbstractMesh (no devices)."""
import jax
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.dist import sharding as shd
from repro.models.common import ParamSpec

MESH = shd.abstract_mesh((16, 16), ("data", "model"))
MESH3 = shd.abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def test_dense_qkv_specs():
    cfg = get_config("granite-20b")       # 48 heads, kv=1 (MQA)
    wq = ParamSpec((6144, 48, 128), ("embed", "heads", "head_dim"))
    assert shd.spec_for(wq, MESH) == P("data", "model", None)
    wk = ParamSpec((6144, 1, 128), ("embed", "kv_heads", "head_dim"))
    # kv=1 cannot shard -> replicated over model (no contraction psum)
    assert shd.spec_for(wk, MESH) == P("data", None, None)


def test_embed_vocab_spec():
    emb = ParamSpec((49152, 6144), ("vocab", "embed"))
    assert shd.spec_for(emb, MESH) == P("model", "data")
    odd = ParamSpec((49155, 6144), ("vocab", "embed"))
    assert shd.spec_for(odd, MESH) == P(None, "data")   # 49155 % 16 != 0


def test_moe_expert_sharding_modes():
    # moonshot: 64 experts -> EP over model
    w = ParamSpec((64, 2048, 1408), ("experts", "embed", "mlp"))
    assert shd.spec_for(w, MESH) == P("model", "data", None)
    # mixtral: 8 experts -> per-expert mlp TP instead
    w8 = ParamSpec((8, 6144, 16384), ("experts", "embed", "mlp"))
    assert shd.spec_for(w8, MESH) == P(None, "data", "model")


def test_no_mesh_axis_used_twice():
    w = ParamSpec((64, 64), ("vocab", "heads"))      # both want "model"
    spec = shd.spec_for(w, MESH)
    flat = [a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    assert len(flat) == len(set(flat))


def test_teacher_rules_drop_fsdp():
    cfg = get_config("granite-3-8b")
    rules = shd.rules_for(cfg, MESH, teacher=True)
    assert "embed" not in rules


def test_zero_extension():
    w = ParamSpec((4096, 32, 128), ("embed", "heads", "head_dim"))
    base = shd.spec_for(w, MESH3)                    # data, model used
    z = shd.zero_extend(w, base, MESH3)
    flat = [a for e in z if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    assert "pod" in flat                             # opt state over pods


def test_head_pad_for():
    assert shd.head_pad_for(get_config("qwen2.5-32b"), 16) == 8   # 40->48
    assert shd.head_pad_for(get_config("granite-20b"), 16) == 0  # 48 ok
    assert shd.head_pad_for(get_config("whisper-small"), 16) == 36
    assert shd.head_pad_for(get_config("mamba2-130m"), 16) == 0


def test_batch_spec_fallbacks():
    assert shd.batch_spec(MESH, 256, 4096) == P(("data",), None)
    assert shd.batch_spec(MESH3, 256, 4096) == P(("pod", "data"), None)
    # B=1 long decode: shard seq instead
    assert shd.batch_spec(MESH3, 1, 524288) == P(None, ("pod", "data"))


def test_kv_cache_spec_fallbacks():
    # kv divisible -> heads sharded
    assert shd.kv_cache_spec(MESH, (128, 32768, 16, 128), "attn") == \
        P(("data",), None, "model", None)
    # kv=1 -> shard the sequence (flash-decoding split)
    assert shd.kv_cache_spec(MESH, (128, 32768, 1, 128), "attn") == \
        P(("data",), "model", None, None)


def test_param_shardings_tree():
    from repro.models import transformer as tf
    cfg = get_config("granite-3-8b")
    specs = tf.lm_specs(cfg)
    tree = shd.param_shardings(specs, MESH)
    leaves = jax.tree_util.tree_leaves(tree)
    assert all(hasattr(l, "spec") for l in leaves)
