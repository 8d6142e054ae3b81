"""Flash attention: Pallas (interpret) and jnp blockwise vs the naive
oracle, swept over shapes/dtypes/masking modes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_lse,
                                           merge_flash_partials)

SHAPES = [
    # B, S, T, H, KV, D
    (2, 128, 128, 4, 2, 16),      # GQA
    (1, 256, 256, 8, 8, 32),      # MHA
    (2, 128, 64, 4, 1, 16),       # MQA, cross lengths
    (1, 64, 64, 6, 3, 8),         # odd group
]


def _qkv(shape, dtype):
    B, S, T, H, KV, D = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, D), dtype)
    return q, k, v


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0),
                                           (True, 32)])
def test_pallas_fwd_sweep(shape, dtype, causal, window):
    q, k, v = _qkv(shape, dtype)
    o_ref = ref.mha_reference(q, k, v, causal=causal, window=window)
    o_pl = flash_attention(q, k, v, causal=causal, window=window,
                           interpret=True, block_q=64, block_kv=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_pl, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("shape", SHAPES[:2])
def test_jnp_flash_fwd(shape):
    q, k, v = _qkv(shape, jnp.float32)
    o_ref = ref.mha_reference(q, k, v, causal=True)
    o = ref.flash_attention_jnp(q, k, v, causal=True, block_q=32,
                                block_kv=32)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5,
                               rtol=2e-5)


def test_pallas_grads_match_oracle():
    q, k, v = _qkv((2, 128, 128, 4, 2, 16), jnp.float32)

    def f_pl(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=True, interpret=True, block_q=64,
            block_kv=64)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(ref.mha_reference(q, k, v, causal=True)))

    g1 = jax.grad(f_pl, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)


def test_segment_ids_packed_sequences():
    B, S, H, KV, D = 2, 96, 4, 2, 16
    q, k, v = _qkv((B, S, S, H, KV, D), jnp.float32)
    seg = jnp.repeat(jnp.arange(3)[None], B, 0).repeat(S // 3, 1)
    o_ref = ref.mha_reference(q, k, v, causal=True, segment_q=seg,
                              segment_kv=seg)
    o = ref.flash_attention_jnp(q, k, v, causal=True, segment_q=seg,
                                segment_kv=seg, block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5,
                               rtol=2e-5)


def test_non_divisible_lengths_pad_path():
    """Odd lengths (e.g. whisper's 1500 frames) must pad, not shrink
    blocks."""
    q, k, v = _qkv((2, 150, 150, 4, 2, 16), jnp.float32)
    for causal in (True, False):
        o_ref = ref.mha_reference(q, k, v, causal=causal)
        o = ref.flash_attention_jnp(q, k, v, causal=causal, block_q=64,
                                    block_kv=64)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", ["seq_not_multiple", "segment_ids",
                                  "lse_seq_not_multiple"])
def test_pallas_raises_where_it_cannot_run(case):
    """A Pallas tier that was asked for never hands the call to jnp."""
    from repro.kernels import ops
    q, k, v = _qkv((1, 96, 96, 4, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="impl='ref'"):
        if case == "seq_not_multiple":
            ops.flash_attention(q, k, v, impl="pallas_interpret",
                                block_q=64, block_kv=64)
        elif case == "segment_ids":
            seg = jnp.zeros((1, 96), jnp.int32)
            ops.flash_attention(q, k, v, segment_q=seg, segment_kv=seg,
                                impl="pallas_interpret")
        else:
            ops.flash_attention_lse(q, k, v, impl="pallas_interpret",
                                    block_q=64, block_kv=64)


def test_q_offset_decode_semantics():
    """q_offset shifts the causal mask (CP shards / decode windows)."""
    B, S, H, KV, D = 1, 64, 2, 2, 8
    q, k, v = _qkv((B, 32, S, H, KV, D), jnp.float32)
    o_ref = ref.mha_reference(q, k, v, causal=True, q_offset=32)
    o = ref.flash_attention_jnp(q, k, v, causal=True, q_offset=32,
                                block_q=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0),
                                           (True, 24)])
@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2)])   # MHA / GQA
@pytest.mark.parametrize("q_offset", [0, 64])
def test_merge_matches_monolithic_contiguous(causal, window, H, KV,
                                             q_offset):
    """Splitting KV into contiguous chunks, flashing each with its
    positions, and merging via merge_flash_partials must equal the
    monolithic call (the overlap-pipelined CP invariant)."""
    B, S, T, D, C = 1, 64, 128, 16, 4
    q, k, v = _qkv((B, S, T, H, KV, D), jnp.float32)
    o_mono = flash_attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, interpret=True,
                             block_q=16, block_kv=16)
    cl = T // C
    parts_o, parts_lse = [], []
    for j in range(C):
        pos = jnp.arange(j * cl, (j + 1) * cl, dtype=jnp.int32)
        oj, lj = flash_attention_lse(
            q, k[:, j * cl:(j + 1) * cl], v[:, j * cl:(j + 1) * cl],
            causal=causal, window=window, q_offset=q_offset,
            kv_positions=pos, interpret=True, block_q=16, block_kv=16)
        parts_o.append(oj)
        parts_lse.append(lj)
    o, _ = merge_flash_partials(parts_o, parts_lse)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_mono),
                               atol=2e-5, rtol=2e-5)


def test_merge_matches_monolithic_strided():
    """Strided chunk positions (the a2a-interleaved layout of the CP
    overlap path: chunk j holds positions d·shard + j·cl + [0, cl) for
    every device d) must also reproduce the monolithic result, forward
    and backward."""
    B, T, H, KV, D = 1, 128, 4, 2, 16
    cp, chunks = 4, 2
    shard, cl = T // cp, T // cp // chunks
    q, k, v = _qkv((B, T, T, H, KV, D), jnp.float32)

    def chunked(q, k, v):
        parts_o, parts_lse = [], []
        for j in range(chunks):
            pos = (np.arange(cp)[:, None] * shard + j * cl
                   + np.arange(cl)[None, :]).reshape(-1)
            sel = jnp.asarray(pos, jnp.int32)
            oj, lj = flash_attention_lse(
                q, jnp.take(k, sel, axis=1), jnp.take(v, sel, axis=1),
                causal=True, kv_positions=sel, interpret=True,
                block_q=16, block_kv=16)
            parts_o.append(oj)
            parts_lse.append(lj)
        return merge_flash_partials(parts_o, parts_lse)[0]

    def mono(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True,
                               block_q=16, block_kv=16)

    np.testing.assert_allclose(np.asarray(chunked(q, k, v)),
                               np.asarray(mono(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    g1 = jax.grad(loss(chunked), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(mono), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_merge_all_masked_chunk_is_inert():
    """A chunk lying entirely in the causal future carries lse ≈ −1e30:
    the merge must weight it to zero (no NaN/garbage leakage) and its
    gradient contribution must be exactly zero."""
    B, S, H, KV, D = 1, 32, 2, 2, 8
    q, k, v = _qkv((B, S, 64, H, KV, D), jnp.float32)
    k_past, v_past = k[:, :32], v[:, :32]
    k_fut, v_fut = k[:, 32:], v[:, 32:]
    pos_past = jnp.arange(32, dtype=jnp.int32)
    pos_fut = jnp.arange(32, 64, dtype=jnp.int32)   # all > max q pos

    def merged(kf, vf):
        o1, l1 = flash_attention_lse(q, k_past, v_past, causal=True,
                                     kv_positions=pos_past,
                                     interpret=True, block_q=16,
                                     block_kv=16)
        o2, l2 = flash_attention_lse(q, kf, vf, causal=True,
                                     kv_positions=pos_fut,
                                     interpret=True, block_q=16,
                                     block_kv=16)
        return merge_flash_partials([o1, o2], [l1, l2])[0]

    o = merged(k_fut, v_fut)
    o_ref = ref.mha_reference(q, k_past, v_past, causal=True)
    assert bool(jnp.all(jnp.isfinite(o)))
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    gk, gv = jax.grad(lambda kf, vf: jnp.sum(merged(kf, vf) ** 2),
                      argnums=(0, 1))(k_fut, v_fut)
    np.testing.assert_array_equal(np.asarray(gk), 0.0)
    np.testing.assert_array_equal(np.asarray(gv), 0.0)


def test_hypothesis_like_random_sweep():
    rng = np.random.default_rng(42)
    for _ in range(6):
        H = int(rng.choice([2, 4, 8]))
        KV = int(rng.choice([g for g in [1, 2, 4, 8] if H % g == 0]))
        D = int(rng.choice([8, 16, 32]))
        S = int(rng.choice([32, 64, 96]))
        q, k, v = _qkv((1, S, S, H, KV, D), jnp.float32)
        o_ref = ref.mha_reference(q, k, v, causal=True)
        o = flash_attention(q, k, v, causal=True, interpret=True,
                            block_q=32, block_kv=32)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=5e-5, rtol=5e-5)
