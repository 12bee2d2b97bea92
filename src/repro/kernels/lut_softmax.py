"""Pallas TPU kernel: row softmax via the paper's LUT pipeline (§VI, eq 10-12).

Fixed-point path (`fixed=True`, the "+Hardware" Table IX configuration):
  per row r:   z_i  = clip(max_j x_rj - x_ri, 0, 10)        (eq 10)
               n_i  = LUT_EXP[z_i * 32]        (Q8.24, ALU_EXP)
               s    = sum_i (n_i >> pre)       (int32-safe accumulate)
               inv  = reciprocal_q24(s) >> pre (ALU_INVERT + range reduce)
               y_i  = fixed_mul(n_i, inv)      (Q8.24 multiply)
matching `repro.core.approx.softmax_lut(fixed=True)` bit-for-bit.

Float path (`fixed=False`): LUT_EXP gather in f32 + true division — the
"LUT softmax, float carry" intermediate the paper describes for the
quantised-but-unaccelerated model (Table IX column 3).

Tiling: one grid step owns a (block_m, N) row-slab so the row reduction
stays on-chip; the 320-entry tables ride along as whole-array VMEM operands
in the [3, 128] lane-segment layout Mosaic can gather from
(``kernels.lut_lookup``).
VMEM at N=32k, bm=8: 8*32768*4 = 1 MB in + 1 MB out (+LUTs) — fine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import approx
from repro.core import fixedpoint as fxp
from repro.core import lut as lutlib
from repro.kernels.lut_lookup import lane_table, lookup

DEFAULT_BLOCK_M = 8


def _reciprocal_q24_body(s_q, inv_tab):
    """reciprocal_q24 (lut.py) inlined for the kernel body (same math)."""
    t = fxp.ilog2(s_q) - fxp.FRAC_BITS
    tp = jnp.maximum(t, 0)
    tn = jnp.maximum(-t, 0)
    m = ((s_q >> tp) << tn).astype(jnp.int32)
    shift = fxp.FRAC_BITS - int(np.log2(lutlib.BINS_PER_UNIT))
    idx = jnp.clip((m >> shift) - 1, 0, lutlib.N_EXP_ENTRIES - 1)
    inv_m = lookup(inv_tab, idx)
    limit = jnp.int32(2**31 - 1) >> tn
    return jnp.where(t >= 0, inv_m >> tp,
                     jnp.where(inv_m > limit, jnp.int32(2**31 - 1),
                               inv_m << tn)).astype(jnp.int32)


def _softmax_kernel_fixed(x_ref, exp_tab_ref, inv_tab_ref, o_ref, *, pre: int):
    x = x_ref[...].astype(jnp.float32)
    exp_tab = exp_tab_ref[...]
    inv_tab = inv_tab_ref[...]
    z = jnp.clip(jnp.max(x, axis=-1, keepdims=True) - x, 0.0, lutlib.EXP_RANGE)
    z_q = jnp.round(z * float(fxp.ONE)).astype(jnp.int32)        # ALU_TO_FIXED
    shift = fxp.FRAC_BITS - int(np.log2(lutlib.BINS_PER_UNIT))
    idx = jnp.clip(z_q >> shift, 0, lutlib.N_EXP_ENTRIES - 1)
    num_q = lookup(exp_tab, idx)                                 # ALU_EXP
    s_q = jnp.sum(approx.pre_shift(num_q, pre), axis=-1, keepdims=True)
    inv_q = _reciprocal_q24_body(s_q, inv_tab) >> pre            # ALU_INVERT
    out_q = fxp.fixed_mul(num_q, inv_q, nonneg=True)
    o_ref[...] = fxp.to_float(out_q).astype(o_ref.dtype)        # ALU_TO_FLOAT


def _softmax_kernel_float(x_ref, exp_tab_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    exp_tab = exp_tab_ref[...]
    z = jnp.clip(jnp.max(x, axis=-1, keepdims=True) - x, 0.0, lutlib.EXP_RANGE)
    idx = jnp.clip((z * lutlib.BINS_PER_UNIT).astype(jnp.int32),
                   0, lutlib.N_EXP_ENTRIES - 1)
    num = lookup(exp_tab, idx)
    o_ref[...] = (num / jnp.sum(num, axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fixed", "block_m", "interpret"))
def lut_softmax_2d(x: jnp.ndarray, *, fixed: bool = True,
                   block_m: int = DEFAULT_BLOCK_M,
                   interpret: bool) -> jnp.ndarray:
    """LUT softmax along the last axis of a [M, N] array."""
    m, n = x.shape
    bm = min(block_m, m)
    assert m % bm == 0, (m, bm)
    bank = lutlib.make_lut_bank()
    pre = max(0, int(np.ceil(np.log2(max(n, 1)))) - 6)
    grid = (m // bm,)
    row_spec = pl.BlockSpec((bm, n), lambda i: (i, 0))
    exp_tab, inv_tab = lane_table(bank.exp_q24), lane_table(bank.inv_q24)
    tab_spec = pl.BlockSpec(exp_tab.shape, lambda i: (0, 0))
    if fixed:
        return pl.pallas_call(
            functools.partial(_softmax_kernel_fixed, pre=pre),
            grid=grid,
            in_specs=[row_spec, tab_spec, tab_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
            interpret=interpret,
        )(x, exp_tab, inv_tab)
    return pl.pallas_call(
        _softmax_kernel_float,
        grid=grid,
        in_specs=[row_spec, tab_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, lane_table(bank.exp_f32))
