"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(128,), (1024,), (4096,), (5000,), (256, 384), (8, 8, 130)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(shape, dtype, seed=0, scale=3.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape) * scale
    return x.astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_histogram_matches_ref(shape, dtype):
    x = _rand(shape, dtype, seed=1)
    mx = jnp.max(jnp.abs(x.astype(jnp.float32)))
    h_k = ops.magnitude_histogram(x, mx, interpret=True)
    h_r = ref.magnitude_histogram(x.astype(jnp.float32), 256, mx)
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))
    assert int(h_k.sum()) == x.size


@pytest.mark.parametrize("ratio", [0.1, 0.35, 0.6, 0.9])
def test_threshold_hits_target_sparsity(ratio):
    x = _rand((20000,), jnp.float32, seed=2)
    thr = ops.topk_threshold(x, jnp.float32(ratio), interpret=True)
    frac = float(jnp.mean(jnp.abs(x) < thr))
    assert abs(frac - ratio) < 0.02      # 256-bin quantization error bound


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_hybrid_compress_matches_ref(shape, dtype):
    x = _rand(shape, dtype, seed=3)
    thr = jnp.float32(1.0)
    kept_k, sign_k, cnt_k, sum_k, max_k = ops.hybrid_compress(
        x, thr, interpret=True)
    xf = x.astype(jnp.float32)
    kept_r, sign_r, cnt_r, sum_r, max_r = ref.hybrid_compress(xf, thr)
    np.testing.assert_allclose(np.asarray(kept_k, np.float32),
                               np.asarray(kept_r), rtol=1e-2, atol=1e-2)
    np.testing.assert_array_equal(np.asarray(sign_k), np.asarray(sign_r))
    assert int(cnt_k) == int(cnt_r)
    np.testing.assert_allclose(float(sum_k), float(sum_r), rtol=1e-3)
    np.testing.assert_allclose(float(max_k), float(max_r), rtol=1e-3)


@pytest.mark.parametrize("shape", SHAPES)
def test_recover_matches_ref(shape):
    x = _rand(shape, jnp.float32, seed=4)
    local = x + 0.2 * _rand(shape, jnp.float32, seed=5, scale=1.0)
    thr = jnp.float32(1.5)
    kept, sign, cnt, ssum, smax = ref.hybrid_compress(x, thr)
    mean = ssum / jnp.maximum(cnt, 1)
    out_k = ops.recover(kept, sign, local, mean, smax, interpret=True)
    out_r = ref.recover(kept, sign, local, mean, smax)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6)


def test_kernels_under_vmap_match_ref():
    """The round engine vmaps the kernels over a chunk of participants."""
    x = _rand((3, 5000), jnp.float32, seed=8)
    local = x + 0.2 * _rand((3, 5000), jnp.float32, seed=9, scale=1.0)
    mx = jnp.max(jnp.abs(x), axis=1)
    hist = jax.vmap(lambda v, m: ops.magnitude_histogram(
        v, m, interpret=True))(x, mx)
    np.testing.assert_array_equal(
        np.asarray(hist),
        np.asarray(jax.vmap(lambda v, m: ref.magnitude_histogram(
            v, 256, m))(x, mx)))
    thr = jnp.array([0.5, 1.0, 2.0], jnp.float32)
    got = jax.vmap(lambda v, t: ops.hybrid_compress(
        v, t, interpret=True))(x, thr)
    want = jax.vmap(ref.hybrid_compress)(x, thr)
    for i in (0, 1, 2, 4):
        np.testing.assert_array_equal(np.asarray(got[i]),
                                      np.asarray(want[i]))
    np.testing.assert_allclose(np.asarray(got[3]), np.asarray(want[3]),
                               rtol=1e-5)
    kept, sign, cnt, ssum, smax = want
    mean = ssum / jnp.maximum(cnt, 1)
    out = jax.vmap(lambda k, g, l, a, m: ops.recover(
        k, g, l, a, m, interpret=True))(kept, sign, local, mean, smax)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(jax.vmap(ref.recover)(kept, sign, local, mean, smax)))


def test_kernel_roundtrip_close_to_core_roundtrip():
    from repro.core import compression as C
    x = _rand((10000,), jnp.float32, seed=6)
    local = x + 0.1 * _rand((10000,), jnp.float32, seed=7, scale=1.0)
    rec_k, _ = ops.hybrid_roundtrip(x, local, jnp.float32(0.5),
                                  interpret=True)
    rec_c, _ = C.hybrid_roundtrip(x, local, jnp.float32(0.5))
    # kernel threshold is 256-bin quantized → identical on ≥99% of slots
    agree = float(jnp.mean(jnp.isclose(rec_k, rec_c, rtol=1e-5)))
    assert agree > 0.95   # 256-bin threshold quantization slack


@pytest.mark.parametrize("b,h,hkv,d,s,blk", [
    (2, 8, 4, 64, 1024, 256),
    (1, 4, 1, 128, 512, 128),
    (3, 6, 6, 32, 768, 256),
])
def test_decode_attention_matches_ref(b, h, hkv, d, s, blk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    length = jnp.asarray(np.random.default_rng(0).integers(1, s + 1, b),
                         jnp.int32)
    o_k = ops.decode_attention(q, k, v, length, kv_block=blk,
                               interpret=True)
    o_r = ref.decode_attention(q, k, v, length)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=3e-5, atol=3e-5)


def test_decode_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 8, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 512, 4, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 512, 4, 64), jnp.bfloat16)
    length = jnp.array([512, 300], jnp.int32)
    o_k = ops.decode_attention(q, k, v, length, kv_block=128,
                               interpret=True)
    o_r = ref.decode_attention(q, k, v, length)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_flash_attention_jnp_matches_dense():
    """Train-path chunked attention == dense softmax attention."""
    from repro.models.layers import flash_attention_jnp
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, s, h, d = 2, 256, 4, 32
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, 2, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, 2, d), jnp.float32)
    out = flash_attention_jnp(q, k, v, causal=True, q_block=64, kv_block=64)
    # dense reference
    kk = jnp.repeat(k, 2, axis=2)
    vv = jnp.repeat(v, 2, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(d)
    mask = np.tril(np.ones((s, s), bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    dense = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)
