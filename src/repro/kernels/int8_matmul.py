"""Pallas TPU kernel: INT8 x INT8 -> INT32 matmul with power-of-2 rescale.

The paper's quantised pipeline (§IV) multiplies INT8 weights by INT8
activations, accumulates into wider integers, and rescales by bit shifts
(eq 9's 2^y scales).  On a v5e the MXU executes int8 x int8 -> int32
natively at 2x the bf16 rate (394 TOPS), so the paper's "no-FPU" trick
becomes a throughput/bandwidth optimisation (DESIGN.md §2).

Tiling: classic (M/bm, N/bn, K/bk) grid, K innermost; an int32 VMEM scratch
tile carries the partial accumulation across K steps; the epilogue applies
the shift rescale (acc_exp -> out_exp) and writes f32 or a clipped int16
residual (the paper's INT16 intermediate type).

MXU alignment: block defaults 128/128/128 (int8 tiles are (32,128)-packed;
multiples of 128 keep the MXU fully fed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _int8_matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int, shift: int,
                        out_int16: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        acc = (acc >> shift) if shift >= 0 else (acc << (-shift))
        if out_int16:
            acc = jnp.clip(acc, -(2**15), 2**15 - 1)
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "shift", "out_int16", "block_m", "block_n", "block_k", "interpret"))
def int8_matmul_raw(x_int: jnp.ndarray, w_int: jnp.ndarray, *, shift: int = 0,
                    out_int16: bool = False,
                    block_m: int = DEFAULT_BM, block_n: int = DEFAULT_BN,
                    block_k: int = DEFAULT_BK,
                    interpret: bool) -> jnp.ndarray:
    """[M,K]i8 @ [K,N]i8 -> int32 (or int16) with epilogue shift ``>> shift``."""
    m, k = x_int.shape
    k2, n = w_int.shape
    assert k == k2
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_k = k // bk
    out_dtype = jnp.int16 if out_int16 else jnp.int32
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_int8_matmul_kernel, n_k=n_k, shift=shift,
                          out_int16=out_int16),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[_acc_scratch(bm, bn)],
        interpret=interpret,
    )(x_int, w_int)


def _acc_scratch(bm: int, bn: int):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM((bm, bn), jnp.int32)
