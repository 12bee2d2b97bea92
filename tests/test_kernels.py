"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py
(pure-jnp oracle), interpret=True on CPU as mandated."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("shape", [(8, 32), (37, 300), (128, 128), (5, 27),
                                   (1, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lut_gelu_sweep(shape, dtype):
    x = (jax.random.normal(KEY, shape) * 3).astype(dtype)
    got = ops.lut_gelu(x, interpret=True)
    want = ref.lut_gelu(x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0, atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("shape", [(4, 27), (13, 99), (8, 320), (3, 1000)])
@pytest.mark.parametrize("fixed", [True, False])
def test_lut_softmax_sweep(shape, fixed):
    x = jax.random.normal(KEY, shape) * 4
    got = ops.lut_softmax(x, fixed=fixed, interpret=True)
    want = ref.lut_softmax(x, fixed=fixed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # sanity vs exact softmax
    assert float(jnp.max(jnp.abs(got - jax.nn.softmax(x, -1)))) < 0.05


def test_lut_softmax_fixed_bit_exact_paper_scale():
    """At the paper's SEQLEN=27 the kernel must match the Q8.24 reference
    bit-for-bit (same LUT indices, same fixed multiply)."""
    x = jax.random.normal(KEY, (16, 27)) * 3
    got = ops.lut_softmax(x, fixed=True, interpret=True)
    want = ref.lut_softmax(x, fixed=True)
    assert jnp.array_equal(got, want)


@pytest.mark.parametrize("shape", [(8, 99), (8, 320), (3, 1000)])
def test_lut_softmax_fixed_bit_exact_long_rows(shape):
    """Past 64 keys the row sum is pre-shifted to stay in int32; the
    kernel rounds that shift like the Q8.24 reference, so longer rows
    (KWT-1's 99, LM caches) stay bit-exact too."""
    x = jax.random.normal(jax.random.fold_in(KEY, shape[1]), shape) * 3
    got = ops.lut_softmax(x, fixed=True, interpret=True)
    assert jnp.array_equal(got, ref.lut_softmax(x, fixed=True))


@pytest.mark.parametrize("mnk", [(8, 16, 32), (50, 70, 200), (128, 128, 128),
                                 (1, 5, 7)])
@pytest.mark.parametrize("residual_bits", [16, 32])
def test_int8_matmul_sweep(mnk, residual_bits):
    m, n, k = mnk
    k1, k2 = jax.random.split(KEY)
    # small magnitudes so INT16 residuals don't saturate (paper sizing)
    x = jax.random.randint(k1, (m, k), -16, 16, jnp.int8)
    w = jax.random.randint(k2, (k, n), -16, 16, jnp.int8)
    got = ops.int8_matmul(x, w, x_exp=5, w_exp=6, out_exp=7,
                          residual_bits=residual_bits, interpret=True)
    want = ref.int8_matmul(x, w, x_exp=5, w_exp=6, out_exp=7,
                           residual_bits=residual_bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_int8_matmul_accepts_stored_qtensors():
    """The full-integer path runs the Pallas kernel directly on stored
    operands — int8 and nibble-packed int4 QTensors — reading exponents
    off the containers (the Engine's integer-resident storage form)."""
    from repro.core import quant

    k1, k2 = jax.random.split(KEY)
    x = jax.random.randint(k1, (8, 32), -16, 16, jnp.int8)
    w4 = jax.random.randint(k2, (32, 16), -8, 8, jnp.int8)
    qx = quant.QTensor(x, 5)
    qw = quant.QTensor.store(w4, 6, bits=4)           # nibble-packed
    assert qw.packed and qw.values.dtype == jnp.uint8
    got = ops.int8_matmul(qx, qw, out_exp=7, interpret=True)
    want = ref.int8_matmul(x, w4, x_exp=5, w_exp=6, out_exp=7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # per-channel axis exponents fold into the epilogue
    axis = jax.random.randint(jax.random.fold_in(KEY, 3), (16,),
                              -2, 3).astype(jnp.int8)
    qwc = quant.QTensor.store(w4, 6, bits=4, axis_exponents=axis)
    got_c = ops.int8_matmul(qx, qwc, out_exp=7, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got_c),
        np.asarray(want * np.exp2(-np.asarray(axis, np.float32))),
        atol=1e-6)


def test_int8_matmul_kernel_bit_matches_jnp_int_exec_path():
    """The Engine's two int-exec flavours are the same math: the Pallas
    kernel (interpret mode) and the jnp emulation quant.int_exec_einsum
    uses on CPU agree BIT-FOR-BIT — int8 x int8, INT16 residual clip,
    po2 requant epilogue, scalar and per-channel."""
    from repro.core import quant

    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 9))
    x = jax.random.normal(k1, (8, 32))
    w = 0.3 * jax.random.normal(k2, (32, 16))
    grid = quant.quantize_po2(w, 6, rounding="nearest").int_values()
    for axis in (None, jax.random.randint(jax.random.fold_in(KEY, 10),
                                          (16,), -2, 3).astype(jnp.int8)):
        qw = quant.QTensor.store(grid, 6, axis_exponents=axis)
        jnp_out = quant.int_exec_einsum("bd,df->bf", x, qw, x_exp=5,
                                        residual_bits=16)
        xi = quant.quantize_act(x, 5).astype(jnp.int8)
        kern = ops.int8_matmul(quant.QTensor(xi, 5), qw,
                               residual_bits=16, interpret=True)
        assert jnp.array_equal(jnp_out, kern), \
            f"kernel vs jnp int-exec diverged (axis={axis is not None})"


@pytest.mark.parametrize("b,hq,hkv,lq,lk,d", [
    (1, 2, 2, 64, 64, 32),       # MHA square
    (2, 4, 2, 64, 64, 32),       # GQA
    (1, 8, 1, 128, 128, 64),     # MQA
    (2, 4, 2, 1, 64, 32),        # decode
    (1, 2, 2, 64, 256, 32),      # long kv (multi-tile online softmax)
])
@pytest.mark.parametrize("causal", [True, False])
def test_lut_attention_sweep(b, hq, hkv, lq, lk, d, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, lq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, lk, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, lk, d), jnp.float32)
    exact = ops.lut_attention(q, k, v, causal=causal, use_lut=False,
                              interpret=True)
    r_exact = ref.lut_attention(q, k, v, causal=causal, softmax_mode="exact")
    np.testing.assert_allclose(np.asarray(exact), np.asarray(r_exact),
                               rtol=2e-5, atol=2e-5)
    lut = ops.lut_attention(q, k, v, causal=causal, use_lut=True,
                            interpret=True)
    r_lut = ref.lut_attention(q, k, v, causal=causal, softmax_mode="lut")
    # multi-tile online-LUT telescopes differently from single-shot LUT:
    # bounded by the LUT bin width (1/32) relative error per factor.
    np.testing.assert_allclose(np.asarray(lut), np.asarray(r_lut),
                               rtol=0.05, atol=0.05)
    # and must stay close to exact attention overall
    assert float(jnp.max(jnp.abs(lut - r_exact))) < 0.06


def test_lut_attention_bf16():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 32, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 32, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 32, 32), jnp.bfloat16)
    out = ops.lut_attention(q, k, v, causal=True, use_lut=True,
                            interpret=True)
    assert out.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
