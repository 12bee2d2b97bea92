"""Public jit'd wrappers for the Pallas kernels.

Handles: arbitrary leading batch dims, padding to block multiples and
dtype plumbing.  Every wrapper takes ``interpret`` explicitly: the
runtime passes its plan-time decision (``runtime.plan_interpret``), and
nothing here probes the backend, so a caller on the chip never lands in
the interpreter by default.

All block geometry goes through two shared helpers:

  ``pad_to_block(x, axis, mult)``  - pad an axis up to a block multiple,
                                     returning the original size for the
                                     final slice-back;
  ``fit_block(size, preferred)``   - shrink a preferred block edge by
                                     powers of two until it divides the
                                     (padded) size.

which every wrapper below (GELU, softmax, matmul, attention) uses instead
of the previously duplicated pad/shrink loops.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import int8_matmul as _mm
from repro.kernels import lut_attention as _attn
from repro.kernels import lut_gelu as _gelu
from repro.kernels import lut_softmax as _sm


def pad_to_block(x: jnp.ndarray, axis: int, mult: int, value=0.0):
    """Pad ``axis`` up to a multiple of ``mult``; returns (padded, size0)
    where ``size0`` is the pre-pad size (for slicing the result back)."""
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x, size
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value), size


def fit_block(size: int, preferred: int) -> int:
    """Largest power-of-two shrink of ``preferred`` that divides ``size``.

    Kernels require the grid to tile the (padded) array exactly; this
    replaces the per-wrapper ``while size % b: b //= 2`` loops.  Always
    >= 1 for positive sizes (1 divides everything).
    """
    assert size > 0 and preferred > 0, (size, preferred)
    b = min(preferred, size)
    while size % b:
        b //= 2
    return max(b, 1)


# Softmax row-slab sizing: keep the live tile around 256k f32 elements
# (1 MB in + 1 MB out of ~16 MB VMEM) while widening the slab for short
# rows — at the paper's K=27 an 8-row slab would mean a grid step per
# 8 rows; 256k/32 lets thousands of rows share one kernel invocation.
_SM_TILE_ELEMS = 1 << 18


def _softmax_block_m(m: int, n: int) -> int:
    target = max(_sm.DEFAULT_BLOCK_M, min(1024, _SM_TILE_ELEMS // max(n, 1)))
    return fit_block(m, target)


def lut_gelu(x: jnp.ndarray, *, interp: bool = False,
             interpret: bool) -> jnp.ndarray:
    """Piecewise LUT GELU over any-shaped input."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    padded, m0 = pad_to_block(flat, 0, 8)
    padded, n0 = pad_to_block(padded, 1, 128)
    bm = fit_block(padded.shape[0], _gelu.DEFAULT_BLOCK_M)
    bn = fit_block(padded.shape[1], _gelu.DEFAULT_BLOCK_N)
    out = _gelu.lut_gelu_2d(padded, interp=interp, block_m=bm, block_n=bn,
                            interpret=interpret)
    return out[:m0, :n0].reshape(shape)


def lut_softmax(x: jnp.ndarray, *, fixed: bool = True,
                interpret: bool) -> jnp.ndarray:
    """LUT softmax along the last axis of any-shaped input.

    Padding rows (axis 0) are whole extra rows and are sliced away before
    returning — real rows never see padding lanes (the key axis is not
    padded), so the wrapper is exact with respect to the 2-D kernel.
    """
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    padded, m0 = pad_to_block(flat, 0, 8)
    bm = _softmax_block_m(padded.shape[0], padded.shape[1])
    out = _sm.lut_softmax_2d(padded, fixed=fixed, block_m=bm,
                             interpret=interpret)
    return out[:m0].reshape(shape)


def int8_matmul(x_int, w_int, *, x_exp: int | None = None,
                w_exp: int | None = None, out_exp: int | None = None,
                residual_bits: int = 32,
                interpret: bool) -> jnp.ndarray:
    """Quantised matmul -> dequantised f32 (contract matches ref.int8_matmul).

    Operands may be raw int arrays (+ explicit exponents) or stored
    ``quant.QTensor``s — int8 or nibble-packed int4 — whose exponents and
    per-channel refinements are read off the container: the full-integer
    pipeline runs the Pallas int8 x int8 -> int32 kernel directly on the
    bytes the Engine keeps resident.
    """
    from repro.core import quant as _q

    w_axis = None
    if isinstance(x_int, _q.QTensor):
        if x_int.axis_exponents is not None:
            # x's axis_exponents scale its LAST axis — the contraction
            # axis here — which cannot fold into a post-matmul rescale.
            raise NotImplementedError(
                "per-channel axis_exponents on the activation operand "
                "vary along the contraction axis; dequantise x instead")
        x_exp = x_int.exponent if x_exp is None else x_exp
        x_int = x_int.int_values()
    if isinstance(w_int, _q.QTensor):
        w_exp = w_int.exponent if w_exp is None else w_exp
        w_axis = w_int.axis_exponents
        w_int = w_int.int_values()
    assert x_exp is not None and w_exp is not None, \
        "raw int operands need explicit x_exp/w_exp"
    m, k = x_int.shape
    k2, n = w_int.shape
    # int8 packs (32, 128) per vreg: the row block is a multiple of 32
    # (or the whole padded row axis), and rows pad up to that block
    bm = min(_mm.DEFAULT_BM, -(-m // 32) * 32)
    xp, _ = pad_to_block(x_int, 0, bm)
    xp, _ = pad_to_block(xp, 1, 128)
    wp, _ = pad_to_block(w_int, 0, 128)
    wp, _ = pad_to_block(wp, 1, 128)
    acc_exp = x_exp + w_exp
    out_exp = acc_exp if out_exp is None else out_exp
    out = _mm.int8_matmul_raw(
        xp, wp, shift=acc_exp - out_exp, out_int16=(residual_bits == 16),
        block_m=bm, interpret=interpret)
    out = out[:m, :n].astype(jnp.float32) * (2.0 ** (-out_exp))
    if w_axis is not None:
        out = out * jnp.exp2(-w_axis.astype(jnp.float32))
    return out


def lut_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, use_lut: bool = True,
                  scale: float | None = None,
                  interpret: bool) -> jnp.ndarray:
    """Flash attention with LUT-exp softmax; [B,H,L,D] GQA layout."""
    lq, lk = q.shape[2], k.shape[2]
    block_q = fit_block(lq, _attn.DEFAULT_BQ)
    block_k = fit_block(lk, _attn.DEFAULT_BK)
    return _attn.lut_attention(
        q, k, v, causal=causal, use_lut=use_lut, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=interpret)
