"""Pallas TPU kernel: piecewise LUT GELU (paper §VI, eq 13, Fig 7, ALU_GELU).

The paper's ALU_GELU is a scalar custom instruction backed by a 32-entry ROM
with identity/zero tails at +1.595 / -1.857.  TPU-native adaptation
(DESIGN.md §2): the 32-entry table is a VMEM-resident operand held as one
128-lane segment (``kernels.lut_lookup``) and the lookup is a per-vreg lane
gather vectorised across the 8x128 VPU lanes; the tails become predicated
selects.

Tiling: the input is viewed as [M, N]; each grid step owns a (block_m,
block_n) VMEM tile plus the whole (tiny) table.  Default tile 256x512 f32 =
512 kB in + 512 kB out, comfortably inside the ~16 MB v5e VMEM with double
buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import lut as lutlib
from repro.kernels.lut_lookup import lane_table, lookup

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 512


def _gelu_kernel(x_ref, tab_ref, o_ref, *, interp: bool):
    x = x_ref[...].astype(jnp.float32)
    tab = tab_ref[...]
    n = lutlib.N_GELU_ENTRIES
    scale = float(n - 1) / (lutlib.GELU_HI - lutlib.GELU_LO)
    t = (x - lutlib.GELU_LO) * scale
    if not interp:
        idx = jnp.clip(jnp.round(t).astype(jnp.int32), 0, n - 1)
        mid = lookup(tab, idx)
    else:
        tc = jnp.clip(t, 0.0, float(n - 1))
        i0 = jnp.clip(jnp.floor(tc).astype(jnp.int32), 0, n - 2)
        frac = tc - i0.astype(jnp.float32)
        mid = lookup(tab, i0) * (1.0 - frac) + lookup(tab, i0 + 1) * frac
    out = jnp.where(x > lutlib.GELU_HI, x,
                    jnp.where(x < lutlib.GELU_LO, 0.0, mid))
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interp", "block_m", "block_n", "interpret"))
def lut_gelu_2d(x: jnp.ndarray, *, interp: bool = False,
                block_m: int = DEFAULT_BLOCK_M, block_n: int = DEFAULT_BLOCK_N,
                interpret: bool) -> jnp.ndarray:
    """LUT GELU over a [M, N] array (padding/reshape handled by ops.py)."""
    m, n = x.shape
    bm, bn = min(block_m, m), min(block_n, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    bank = lutlib.make_lut_bank()
    tab = lane_table(bank.gelu_f32)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_gelu_kernel, interp=interp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec(tab.shape, lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, tab)
