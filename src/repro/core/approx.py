"""LUT-approximated nonlinearities (paper §VI) as composable JAX functions.

These are the *reference* (pure-jnp) realisations of the paper's five custom
ALU behaviours (Table VII), in both float32 and Q8.24 fixed-point.  The
Pallas kernels in ``repro.kernels`` execute the same math tile-by-tile and
are verified against these functions.

Dispatch contract used across the framework:

    approx.softmax(x, mode=...)   mode in {"exact", "lut", "lut_fixed"}
    approx.gelu(x, mode=...)      mode in {"exact", "lut", "lut_interp"}
    approx.silu(x, mode=...)      (beyond-paper: same bounded-domain method
                                   applied to SiLU-family archs; DESIGN §3)

"exact"      - standard float op (the paper's un-accelerated C path).
"lut"        - float LUT gather (tables identical to the ROM contents).
"lut_fixed"  - full Q8.24 integer pipeline (the "+Hardware" path, Table IX).
"pallas"     - the same Q8.24 pipeline executed by the Pallas kernels in
               ``repro.kernels`` (interpret vs Mosaic is the ``interpret``
               argument, pinned once at plan time by ``repro.runtime`` via
               ``cfg.kernel_interpret`` — never probed per call).

Every non-exact mode is wrapped in a straight-through estimator
(:func:`ste`): the forward value is the approx pipeline verbatim
(bit-identical — custom_vjp primals trace the same ops), while
``jax.grad`` sees the exact float op's vjp.  This is what lets
``repro.qat`` put the deployed LUT numerics inside the training loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fxp
from repro.core import lut as lutlib
from repro.telemetry import taps as _health


def ste(primal_fn, smooth_fn):
    """Straight-through estimator: forward is ``primal_fn`` verbatim (the
    LUT / fixed-point / kernel pipeline, bit-identical to calling it
    directly), backward is the vjp of ``smooth_fn`` (the exact float op)
    evaluated at the same input.

    This is what makes every approx mode usable inside ``jax.grad``
    (repro.qat trains through the deployed numerics): the LUT gathers and
    integer ops have zero/undefined gradients, so QAT follows the standard
    STE reading — quantised forward, smooth backward (arXiv:2009.04465).

    ``primal_fn``/``smooth_fn`` must not close over traced values — a
    captured tracer escapes the custom_vjp when the bwd re-runs under
    ``jax.remat``/``scan``.  Operands beyond ``x`` (e.g. the attention
    mask) go through :func:`ste_masked` as explicit arguments.
    """
    @jax.custom_vjp
    def f(x):
        return primal_fn(x)

    def fwd(x):
        return primal_fn(x), x

    def bwd(x, g):
        _, vjp = jax.vjp(smooth_fn, x)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def ste_masked(primal_fn, smooth_fn):
    """STE over ``(x, mask)``: the (possibly traced) boolean mask is an
    explicit non-differentiable operand — closing over it instead leaks
    the tracer out of the custom_vjp under ``jax.remat``/``scan`` (the
    LM QAT train step rematerialises every block).  Its cotangent is the
    float0 zero JAX expects for bool primals."""
    @jax.custom_vjp
    def f(x, mask):
        return primal_fn(x, mask)

    def fwd(x, mask):
        return primal_fn(x, mask), (x, mask)

    def bwd(res, g):
        x, mask = res
        _, vjp = jax.vjp(lambda v: smooth_fn(v, mask), x)
        return vjp(g)[0], np.zeros(mask.shape, jax.dtypes.float0)

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# SoftMax (paper eqs 2, 10, 11, 12)
# ---------------------------------------------------------------------------

def softmax_exact(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    return jax.nn.softmax(x.astype(jnp.float32), axis=axis)


def pre_shift(num_q: jnp.ndarray, pre: int) -> jnp.ndarray:
    """Round-to-nearest right shift of the Q8.24 numerators.  Truncating
    here instead biases every lane low by ~2^{pre-1}, which deflates the
    row sum and turns into a +8% normalisation overshoot at K=32k."""
    if pre <= 0:
        return num_q
    return (num_q + (1 << (pre - 1))) >> pre


def softmax_lut(x: jnp.ndarray, axis: int = -1, *, fixed: bool = False,
                range_reduce: bool = True,
                bank: lutlib.LutBank | None = None) -> jnp.ndarray:
    """Max-normalised LUT softmax (eq 10 with the eq-11/12 tables).

    z_i = clip(max(x) - x_i, 0, 10);  num_i = LUT_EXP[z_i*32]
    s = sum_i num_i;                  out_i = num_i * LUT_INV-based 1/s
    """
    bank = bank or lutlib.make_lut_bank()
    x = x.astype(jnp.float32)
    z = jnp.clip(jnp.max(x, axis=axis, keepdims=True) - x, 0.0, lutlib.EXP_RANGE)
    if not fixed:
        num = jnp.take(jnp.asarray(bank.exp_f32),
                       jnp.clip((z * lutlib.BINS_PER_UNIT).astype(jnp.int32),
                                0, lutlib.N_EXP_ENTRIES - 1))
        s = jnp.sum(num, axis=axis, keepdims=True)
        if range_reduce:
            inv = 1.0 / s  # float path: true division, LUT only for exp
        else:
            inv = jnp.take(jnp.asarray(bank.inv_f32),
                           lutlib.inv_index_from_q24(fxp.to_fixed(s)))
        return num * inv

    # Q8.24 integer pipeline: ALU_TO_FIXED -> ALU_EXP -> sum -> ALU_INVERT
    # -> fixed multiply -> ALU_TO_FLOAT.  Matches the C loop in §VI.
    #
    # The paper's int32 accumulator holds sums up to K=SEQLEN=27 in Q8.24;
    # beyond K=127 it would overflow.  For framework sequence lengths we
    # pre-shift the numerators by `pre` bits so the row sum stays in int32,
    # and compensate in the reciprocal (1/(s<<pre) == (1/s)>>pre).  pre==0
    # reproduces the paper bit-exactly at its own scales.
    k_len = x.shape[axis]
    pre = max(0, int(np.ceil(np.log2(max(k_len, 1)))) - 6)
    z_q = fxp.to_fixed(z)
    num_q = jnp.take(jnp.asarray(bank.exp_q24),
                     lutlib.exp_index_from_q24(z_q))             # in [0, 1]
    s_q = jnp.sum(pre_shift(num_q, pre), axis=axis, keepdims=True)  # Q8.(24-pre)
    inv_q = lutlib.reciprocal_q24(s_q, bank, range_reduce=range_reduce)
    inv_q = inv_q >> pre                                          # back to Q8.24
    out_q = fxp.fixed_mul(num_q, inv_q, nonneg=True)
    return fxp.to_float(out_q)


def softmax(x: jnp.ndarray, axis: int = -1, mode: str = "exact",
            interpret: bool = False, **kw) -> jnp.ndarray:
    # quantisation-health tap (telemetry.taps): trace-time no-op unless an
    # Engine taps program is collecting.  Placed in the dispatcher — never
    # inside the ste() custom_vjp primal, whose inner trace's tracers must
    # not leak into the aux output.
    if _health.active() and axis in (-1, x.ndim - 1):
        _health.tap_softmax(x, None, fixed=mode in ("lut_fixed", "pallas"))
    if mode == "exact":
        return softmax_exact(x, axis)
    if mode == "lut":
        primal = lambda v: softmax_lut(v, axis, fixed=False, **kw)
    elif mode == "lut_fixed":
        primal = lambda v: softmax_lut(v, axis, fixed=True, **kw)
    elif mode == "pallas":
        assert axis in (-1, x.ndim - 1), "pallas softmax reduces the last axis"
        from repro.kernels import ops
        primal = lambda v: ops.lut_softmax(v, fixed=True, interpret=interpret)
    else:
        raise ValueError(f"unknown softmax mode {mode!r}")
    return ste(primal, lambda v: softmax_exact(v, axis))(x)


def masked_softmax(s: jnp.ndarray, mask: jnp.ndarray | None,
                   mode: str = "exact", interpret: bool = False) -> jnp.ndarray:
    """Softmax over the last axis with *structural* masking.

    For the LUT modes, masked lanes are excluded from the numerator sum
    (they never reach the ROM), mirroring the paper's C pipeline which only
    computes valid entries — not approximated to e^{-10} by the clip.
    Rows that are fully masked return zeros.
    """
    if _health.active():   # health tap; see softmax() for placement notes
        _health.tap_softmax(s, mask, fixed=mode in ("lut_fixed", "pallas"))
    if mode == "exact" and s.dtype == jnp.bfloat16:
        # dtype-preserving path: the materialised score/prob tensors stay
        # bf16 (halved HBM traffic — §Perf H1); row stats reduce in f32.
        neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.bfloat16)
        sm = s if mask is None else jnp.where(mask, s, neg)
        m = jnp.max(sm.astype(jnp.float32), axis=-1, keepdims=True)
        p = jnp.exp(sm - m.astype(jnp.bfloat16))
        if mask is not None:
            p = jnp.where(mask, p, 0)
        den = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
        return p * (1.0 / jnp.maximum(den, 1e-30)).astype(jnp.bfloat16)
    s = s.astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min

    def exact_f32(sv, mk):
        sm = sv if mk is None else jnp.where(mk, sv, neg)
        out = jax.nn.softmax(sm, axis=-1)
        return out if mk is None else jnp.where(mk, out, 0.0)

    if mode == "exact":
        return exact_f32(s, mask)
    if mode == "pallas":
        # Kernel path: unmasked rows are the Pallas LUT pipeline verbatim
        # (bit-identical to ops.lut_softmax).  With a mask, masked lanes
        # enter the kernel at the z=10 clip bin (the paper's own off-range
        # leak); we zero them and renormalise in f32, recovering the
        # structural exclusion of the jnp reference up to that rescale.
        from repro.kernels import ops

        def primal(sv, mk):
            sm = sv if mk is None else jnp.where(mk, sv, neg)
            out = ops.lut_softmax(sm, fixed=True, interpret=interpret)
            if mk is not None:
                out = jnp.where(mk, out, 0.0)
                out = out / jnp.maximum(
                    jnp.sum(out, axis=-1, keepdims=True), 1e-30)
            return out
    elif mode == "lut":
        def primal(sv, mk):
            bank = lutlib.make_lut_bank()
            sm = sv if mk is None else jnp.where(mk, sv, neg)
            m = jnp.max(sm, axis=-1, keepdims=True)
            z = jnp.clip(m - sv, 0.0, lutlib.EXP_RANGE)
            num = jnp.take(
                jnp.asarray(bank.exp_f32),
                jnp.clip((z * lutlib.BINS_PER_UNIT).astype(jnp.int32),
                         0, lutlib.N_EXP_ENTRIES - 1))
            if mk is not None:
                num = jnp.where(mk, num, 0.0)
            return num / jnp.maximum(
                jnp.sum(num, axis=-1, keepdims=True), 1e-30)
    elif mode == "lut_fixed":
        def primal(sv, mk):
            bank = lutlib.make_lut_bank()
            sm = sv if mk is None else jnp.where(mk, sv, neg)
            m = jnp.max(sm, axis=-1, keepdims=True)
            z = jnp.clip(m - sv, 0.0, lutlib.EXP_RANGE)
            k_len = sv.shape[-1]
            pre = max(0, int(np.ceil(np.log2(max(k_len, 1)))) - 6)
            z_q = fxp.to_fixed(z)
            num_q = jnp.take(jnp.asarray(bank.exp_q24),
                             lutlib.exp_index_from_q24(z_q))
            if mk is not None:
                num_q = jnp.where(mk, num_q, 0)
            s_q = jnp.sum(pre_shift(num_q, pre), axis=-1, keepdims=True)
            s_q = jnp.maximum(s_q, 1)
            inv_q = lutlib.reciprocal_q24(s_q, bank) >> pre
            return fxp.to_float(fxp.fixed_mul(num_q, inv_q, nonneg=True))
    else:
        raise ValueError(f"unknown softmax mode {mode!r}")
    # STE: the approx pipeline verbatim in the forward pass, the exact
    # masked softmax's gradient in the backward pass (QAT trains through
    # the deployed numerics; see repro.qat).  The mask — often a tracer
    # built inside the same remat'd block — is an explicit operand, never
    # a closure capture.
    if mask is None:
        return ste(lambda sv: primal(sv, None),
                   lambda sv: exact_f32(sv, None))(s)
    return ste_masked(primal, exact_f32)(s, mask)


# ---------------------------------------------------------------------------
# GELU (paper eqs 7, 13, Fig 7)
# ---------------------------------------------------------------------------

def gelu_exact(x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.gelu(x.astype(jnp.float32), approximate=False)


def gelu_lut(x: jnp.ndarray, *, interp: bool = False,
             bank: lutlib.LutBank | None = None) -> jnp.ndarray:
    """Piecewise GELU: x above 1.595, 0 below -1.857, 32-entry LUT between."""
    bank = bank or lutlib.make_lut_bank()
    x = x.astype(jnp.float32)
    if not interp:
        mid = jnp.take(jnp.asarray(bank.gelu_f32), lutlib.gelu_index_from_f32(x))
    else:
        # beyond-paper: linear interpolation between adjacent entries.
        n = lutlib.N_GELU_ENTRIES
        t = (x - lutlib.GELU_LO) * (float(n - 1) / (lutlib.GELU_HI - lutlib.GELU_LO))
        t = jnp.clip(t, 0.0, float(n - 1))
        i0 = jnp.clip(jnp.floor(t).astype(jnp.int32), 0, n - 2)
        frac = t - i0.astype(jnp.float32)
        tab = jnp.asarray(bank.gelu_f32)
        mid = jnp.take(tab, i0) * (1.0 - frac) + jnp.take(tab, i0 + 1) * frac
    return jnp.where(x > lutlib.GELU_HI, x,
                     jnp.where(x < lutlib.GELU_LO, 0.0, mid))


def gelu(x: jnp.ndarray, mode: str = "exact", interpret: bool = False,
         **kw) -> jnp.ndarray:
    if _health.active():   # health tap; see softmax() for placement notes
        _health.tap_gelu(x)
    if mode == "exact":
        return gelu_exact(x)
    if mode == "lut":
        primal = lambda v: gelu_lut(v, interp=False, **kw)
    elif mode == "lut_interp":
        primal = lambda v: gelu_lut(v, interp=True, **kw)
    elif mode == "pallas":
        from repro.kernels import ops
        primal = lambda v: ops.lut_gelu(v, interpret=interpret)
    else:
        raise ValueError(f"unknown gelu mode {mode!r}")
    return ste(primal, gelu_exact)(x)


# ---------------------------------------------------------------------------
# Beyond-paper: the same bounded-domain LUT method for SiLU / sigmoid /
# softplus, covering the assigned SiLU-family and SSM archs (DESIGN §3).
# ---------------------------------------------------------------------------

_SIG_RANGE = 8.0
_SIG_ENTRIES = 256


def _sigmoid_table() -> jnp.ndarray:
    import numpy as np

    z = np.linspace(-_SIG_RANGE, _SIG_RANGE, _SIG_ENTRIES)
    return jnp.asarray(1.0 / (1.0 + np.exp(-z)), jnp.float32)


def sigmoid_lut(x: jnp.ndarray) -> jnp.ndarray:
    tab = _sigmoid_table()
    t = (x.astype(jnp.float32) + _SIG_RANGE) * ((_SIG_ENTRIES - 1) / (2 * _SIG_RANGE))
    idx = jnp.clip(jnp.round(t).astype(jnp.int32), 0, _SIG_ENTRIES - 1)
    mid = tab[idx]
    return jnp.where(x > _SIG_RANGE, 1.0, jnp.where(x < -_SIG_RANGE, 0.0, mid))


def silu(x: jnp.ndarray, mode: str = "exact") -> jnp.ndarray:
    if mode == "exact":
        return jax.nn.silu(x.astype(jnp.float32))
    return ste(lambda v: v.astype(jnp.float32) * sigmoid_lut(v),
               lambda v: jax.nn.silu(v.astype(jnp.float32)))(x)


def softplus(x: jnp.ndarray, mode: str = "exact") -> jnp.ndarray:
    if mode == "exact":
        return jax.nn.softplus(x.astype(jnp.float32))
    # softplus(x) = x + softplus(-x); bounded branch via -log(sigmoid(-x)).
    return jnp.where(x > _SIG_RANGE, x.astype(jnp.float32),
                     -jnp.log(jnp.maximum(sigmoid_lut(-x), 1e-12)))


def sqrelu(x: jnp.ndarray) -> jnp.ndarray:
    """Squared ReLU (nemotron-4).  Cheap polynomial; no LUT needed (DESIGN §3)."""
    r = jnp.maximum(x, 0.0)
    return r * r


def activation(name: str, mode: str = "exact", interpret: bool = False):
    """Resolve an activation by config name, honouring the approx mode.
    ``interpret`` only applies to the pallas kernel mode (pinned at plan
    time by repro.runtime); SiLU-family pallas requests fall back to the
    jnp LUT reference (the paper's kernel set covers GELU + softmax)."""
    if name == "gelu":
        if mode == "pallas":
            return lambda x: gelu(x, mode="pallas", interpret=interpret)
        return lambda x: gelu(x, mode="lut" if mode != "exact" else "exact")
    if name == "silu":
        return lambda x: silu(x, mode="lut" if mode == "pallas" else mode)
    if name == "sqrelu":
        return lambda x: sqrelu(x)
    if name == "relu":
        return lambda x: jnp.maximum(x, 0.0)
    raise ValueError(f"unknown activation {name!r}")
