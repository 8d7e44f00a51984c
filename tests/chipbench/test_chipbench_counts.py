"""FLOPs counted from shapes against hand counts, and each kernel's bytes
from a vector's length."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops as FL
from chipbench import kernels as K
from chipbench.configs import cnn_har


def conv(out_elems, k):
    return 2 * out_elems * k


def test_cnn_har_forward_by_hand():
    want = (conv(64 * 32, 5 * 9) + conv(32 * 64, 5 * 32)
            + conv(16 * 64, 5 * 64) + 2 * 1024 * 128 + 2 * 128 * 6)
    p = jax.eval_shape(lambda k: cnn_har.init(k), jax.random.PRNGKey(0))
    assert FL.forward_flops_per_sample(cnn_har.apply, p, (128, 9)) == \
        want == 1_758_720


def test_parameter_counts():
    p = jax.eval_shape(lambda k: cnn_har.init(k), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(p)) == 164_134


def test_scan_and_batch_multiply():
    w = jax.ShapeDtypeStruct((16, 8), jnp.float32)

    def f(w, x):
        def body(c, _):
            return c @ w.T @ w, None
        return jax.lax.scan(body, x, None, length=5)[0]

    x = jax.ShapeDtypeStruct((3, 8), jnp.float32)
    assert FL.flops(f, w, x) == 5 * (2 * 3 * 16 * 8 + 2 * 3 * 8 * 16)


@pytest.mark.parametrize("n,tiles", [
    (1, 32 * 128),                       # one block of 32 rows
    (128 * 32, 32 * 128),
    (128 * 32 + 1, 64 * 128),            # rows rounded to 32
    (11_164_362, 171 * 512 * 128),       # ResNet-18: 171 blocks of 512 rows
    (164_134, 512 * 128 * 3)])           # CNN-H: 1283 rows -> 3 blocks
def test_tile_elements(n, tiles):
    assert K.tile_elements(n) == tiles


def test_call_bytes():
    e = K.tile_elements(164_134)
    assert K.call_bytes("hybrid_compress", 164_134, 4) == 4 * e * (4 + 4 + 1)
    assert K.call_bytes("recover", 164_134, 4) == \
        4 * e * (4 + 1 + 4 + 4)
    assert K.call_bytes("magnitude_histogram", 164_134, 2) == \
        2 * e * 4 + 2 * 256 * 128 * 4 + 4


def test_least_time_is_hbm_bound():
    pk = K.peaks("TPU v5 lite")
    b = K.call_bytes("recover", 11_164_362, 8)
    assert K.least_seconds("recover", b, pk) == pytest.approx(b / 819e9)


@pytest.mark.parametrize("kernel", ["magnitude_histogram", "hybrid_compress"])
def test_roofline_reader(kernel):
    b = K.call_bytes(kernel, 164_134, 4)
    least = b / 819e9
    ctx = {"device": {"kind": "TPU v5 lite"},
           "trace": {"kernels": {kernel: {"calls": 1, "seconds": 4 * least,
                                          "bytes": b}}}}
    assert K.roofline_reader(kernel)(ctx) == pytest.approx(25.0)
    ctx["trace"]["kernels"] = {}
    assert K.roofline_reader(kernel)(ctx) is None
