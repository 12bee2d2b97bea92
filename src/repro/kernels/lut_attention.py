"""Pallas TPU kernel: flash-style attention with the paper's LUT softmax.

The paper's key numerical trick — max-normalised softmax (eq 10) so that
exp() has the bounded domain [0,10] servable by a 320-entry ROM — composes
*exactly* with online-softmax (flash) tiling: the running row max IS the
paper's max(x), and the rescale factor applied when the running max changes,
e^{-(m_new - m_old)}, is itself one more LUT_EXP lookup.  This kernel is the
TPU-native reading of the paper's ALU_EXP acceleration (DESIGN.md §2):
instead of one scalar ROM probe per element on a 50 MHz Ibex, the table sits
in VMEM and the probe vectorises over an 8x128 VREG tile, inside a kernel
that never materialises the [Lq, Lk] score matrix in HBM.

Layout: q [B, Hq, Lq, D], k/v [B, Hkv, Lk, D] (GQA: Hq % Hkv == 0).
Grid (B, Hq, Lq/bq, Lk/bk), KV innermost; VMEM scratch carries the running
(m, l, acc) across KV steps.  Causal masking is structural: masked lanes
contribute 0 to the numerator sum (no -inf arithmetic, no e^{-10} leak).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lut as lutlib
from repro.kernels.lut_lookup import lane_table, lookup

DEFAULT_BQ = 128
DEFAULT_BK = 128
_NEG = -1e30


def _lut_exp_f32(z, tab):
    """e^{-z} for z >= 0 via the 320-entry ROM (eq 11), f32 carry."""
    idx = jnp.clip((z * lutlib.BINS_PER_UNIT).astype(jnp.int32),
                   0, lutlib.N_EXP_ENTRIES - 1)
    return lookup(tab, idx)


def _attn_kernel(q_ref, k_ref, v_ref, tab_ref, o_ref, m_ref, l_ref, acc_ref,
                 *, scale: float, causal: bool, n_kv: int, bq: int, bk: int,
                 lq: int, lk: int, use_lut: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)              # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        # query row r attends key c iff (global q pos) >= (global k pos),
        # with queries right-aligned against keys (decode-friendly).
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
            + (lk - lq)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = qpos >= kpos
        s = jnp.where(valid, s, _NEG)

    m_old = m_ref[...]                                # [bq, 1]
    m_tile = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_old, m_tile)
    z = jnp.clip(m_new - s, 0.0, lutlib.EXP_RANGE)
    if use_lut:
        p = _lut_exp_f32(z, tab_ref[...])
        alpha = _lut_exp_f32(jnp.clip(m_new - m_old, 0.0, lutlib.EXP_RANGE),
                             tab_ref[...])
    else:
        p = jnp.exp(-z)
        alpha = jnp.exp(-jnp.clip(m_new - m_old, 0.0, lutlib.EXP_RANGE))
    if causal:
        p = jnp.where(valid, p, 0.0)                  # structural mask
    else:
        p = jnp.where(s <= _NEG / 2, 0.0, p)

    v = v_ref[0, 0].astype(jnp.float32)               # [bk, D]
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "use_lut", "scale", "block_q", "block_k", "interpret"))
def lut_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, use_lut: bool = True,
                  scale: float | None = None,
                  block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                  interpret: bool) -> jnp.ndarray:
    """Flash attention with LUT-exp online softmax.  GQA-aware."""
    b, hq, lq, d = q.shape
    _, hkv, lk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    assert lq % bq == 0 and lk % bk == 0, (lq, lk, bq, bk)
    n_kv = lk // bk
    grid = (b, hq, lq // bq, n_kv)
    tab = lane_table(lutlib.make_lut_bank().exp_f32)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, n_kv=n_kv, bq=bq, bk=bk,
        lq=lq, lk=lk, use_lut=use_lut)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, i, kk: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, i, kk, group=group: (bb, h // group, kk, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, i, kk, group=group: (bb, h // group, kk, 0)),
            pl.BlockSpec(tab.shape, lambda bb, h, i, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bb, h, i, kk: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, tab)
