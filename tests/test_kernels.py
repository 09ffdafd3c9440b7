"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (kernels interpreted on the CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import Q2_14, QFormat, qmatmul_ref, quantize
from repro.core.tiling import MatmulBlock
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def _rand(shape, dtype=jnp.float32, scale=1.0, key=KEY):
    k = jax.random.fold_in(key, hash(shape) % (2**31))
    return (jax.random.normal(k, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# matmul (float)
# ---------------------------------------------------------------------------

MM_SHAPES = [
    (8, 8, 8),
    (32, 16, 24),
    (100, 60, 36),  # non-multiples -> internal padding
    (128, 256, 64),
    (257, 129, 511),  # primes
    (1, 128, 128),
]


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_fp_vs_ref(m, k, n, dtype):
    x = _rand((m, k), dtype)
    w = _rand((k, n), dtype)
    out = ops.matmul_fp(x, w)
    want = ref.matmul_ref(x, w)
    assert out.dtype == want.dtype
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_matmul_fp_custom_block():
    x = _rand((64, 96))
    w = _rand((96, 80))
    out = ops.matmul_fp(x, w, block=MatmulBlock(32, 128, 128))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(x, w)),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# matmul (Q2.14 fixed point)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(16, 16, 16), (64, 100, 48), (33, 57, 65)])
@pytest.mark.parametrize("fmt", [Q2_14, QFormat(4, 12), QFormat(8, 8)])
def test_matmul_q16_vs_ref(m, k, n, fmt):
    # keep products small enough that int32 accumulation cannot overflow
    x = _rand((m, k), scale=0.2)
    w = _rand((k, n), scale=0.2)
    xq, wq = quantize(x, fmt), quantize(w, fmt)
    out = ops.matmul_q16(xq, wq, fmt=fmt)
    want = ref.matmul_q16_ref(xq, wq, fmt)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ---------------------------------------------------------------------------
# skinny-M blocks (an FC head at batch 1-8; fc0's reduction cut to 3584)
# ---------------------------------------------------------------------------

#: One 8-row block, weight tiles of the skinny-M branch: two k steps (the
#: accumulator carries) by two n blocks.
SKINNY_BLOCK = MatmulBlock(8, 256, 1792)


@pytest.mark.parametrize("m", [1, 8])
@pytest.mark.parametrize("epilogue", ["plain", "bias_relu", "wide"])
def test_matmul_q16_skinny_block_bit_exact(m, epilogue):
    k, n, fmt = 3584, 512, Q2_14
    x = _rand((m, k), scale=0.05)
    w = _rand((k, n), scale=0.05)
    xq, wq = quantize(x, fmt), quantize(w, fmt)
    bq = quantize(_rand((n,), scale=0.5), fmt)
    acc = jnp.dot(xq.astype(jnp.int32), wq.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    if epilogue == "plain":
        out = ops.matmul_q16(xq, wq, fmt=fmt, block=SKINNY_BLOCK)
        want = qmatmul_ref(xq, wq, fmt)
    elif epilogue == "bias_relu":
        out = ops.matmul_q16(xq, wq, bias=bq, relu=True, fmt=fmt, block=SKINNY_BLOCK)
        want = ref.matmul_q16_fused_ref(xq, wq, bq, fmt=fmt, relu=True)
    else:
        out = ops.matmul_q16(xq, wq, bias=bq, relu=True, wide=True, fmt=fmt,
                             block=SKINNY_BLOCK)
        want = jnp.maximum(acc + (bq.astype(jnp.int32) << fmt.frac_bits), 0)
    assert out.shape == (m, n) and out.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("m", [1, 8])
def test_matmul_fp_skinny_block(m):
    k, n = 3584, 512
    x, w, b = _rand((m, k)), _rand((k, n)), _rand((n,))
    out = ops.matmul_fp(x, w, bias=b, relu=True, block=SKINNY_BLOCK)
    want = ref.matmul_fused_ref(x, w, b, relu=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------

CONV_CASES = [
    # n, h, w, cin, cout, k, stride, pad
    (1, 8, 8, 3, 8, 3, 1, 0),
    (2, 12, 12, 4, 16, 3, 1, 1),
    (1, 16, 16, 8, 8, 5, 1, 2),
    (2, 32, 32, 3, 16, 11, 4, 2),  # AlexNet-conv1-like: strided -> im2col path
    (1, 9, 9, 2, 6, 2, 2, 0),
]


@pytest.mark.parametrize("n,h,w,cin,cout,k,stride,pad", CONV_CASES)
def test_conv2d_vs_ref(n, h, w, cin, cout, k, stride, pad):
    x = _rand((n, h, w, cin))
    wt = _rand((k, k, cin, cout), scale=0.3)
    out = ops.conv2d(x, wt, stride=stride, padding=pad)
    want = ref.conv2d_ref(x, wt, stride=stride, padding=pad)
    assert out.shape == want.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # b, hq, hkv, sq, sk, d, causal
    (1, 4, 4, 64, 64, 32, True),
    (2, 8, 2, 64, 64, 32, True),   # GQA
    (1, 4, 1, 128, 128, 64, True),  # MQA
    (2, 4, 4, 64, 64, 32, False),
    (1, 2, 2, 96, 96, 32, True),   # non-multiple of block
]


@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,causal", FA_CASES)
def test_flash_attention_vs_ref(b, hq, hkv, sq, sk, d, causal):
    q = _rand((b, hq, sq, d), scale=0.5)
    k = _rand((b, hkv, sk, d), scale=0.5)
    v = _rand((b, hkv, sk, d), scale=0.5)
    out = ops.flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    g = hq // hkv
    qf = q.reshape(b, hkv, g, sq, d).reshape(b * hq, sq, d)
    kf = jnp.broadcast_to(k[:, :, None], (b, hkv, g, sk, d)).reshape(b * hq, sk, d)
    vf = jnp.broadcast_to(v[:, :, None], (b, hkv, g, sk, d)).reshape(b * hq, sk, d)
    want = ref.attention_ref(qf, kf, vf, causal=causal).reshape(b, hq, sq, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-3, rtol=2e-3)


def test_flash_attention_q_offset():
    """Decode-style: 16 query rows appended at the end of 64 keys."""
    b, h, d, sk, sq = 1, 2, 32, 64, 16
    q = _rand((b, h, sq, d), scale=0.5)
    k = _rand((b, h, sk, d), scale=0.5)
    v = _rand((b, h, sk, d), scale=0.5)
    out = ops.flash_attention(q, k, v, causal=True, q_offset=sk - sq,
                              bq=16, bk=16)
    want = ref.attention_ref(
        q.reshape(b * h, sq, d), k.reshape(b * h, sk, d), v.reshape(b * h, sk, d),
        causal=True, q_offset=sk - sq,
    ).reshape(b, h, sq, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-3, rtol=2e-3)
