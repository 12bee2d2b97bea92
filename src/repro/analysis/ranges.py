"""Q8.24 interval analysis: static overflow / precondition verification.

An abstract interpreter over jaxprs where every variable carries a value
interval ``[lo, hi]`` (exact Python ints for integer dtypes, floats for
float dtypes).  Constants — notably the LUT ROM tables from
``core/lut.py`` — enter with their concrete min/max, which is what makes
the analysis precise enough to verify the fixed-point pipelines: a gather
from ``LUT_EXP`` is *provably* in ``[e^-9.97, 1.0]`` in Q8.24 no matter
how wild the index interval is.

Checks performed while interpreting:

  * **int32 overflow**: every integer ``add``/``sub``/``mul``/
    ``reduce_sum``/``dot_general``/``shift_left`` whose exact mathematical
    result interval escapes the operand dtype's range.  A ``shift_left``
    (or any arithmetic op) whose result feeds ONLY ``select_n`` choice
    lanes is recognised as the repo's saturating-guard idiom
    (``jnp.where(a > limit, MAX, a << s)``) and reported as
    ``whitelisted`` instead — the wrapped value is statically dead.
  * **fixed_mul precondition**: the 12/12-limb product is exact only for
    24-bit magnitudes (``|a|,|b| <= 1.0`` in Q8.24).  The ``abs`` eqns
    inside ``fixed_mul`` are checked against ``ONE``; a violated bound is
    exactly the silent-wrap class the PR-5 review feared.

Verification is compositional (assume-guarantee): ``check_ranges`` runs
one contract per pipeline stage with declared input intervals (reported
as ``assumption`` findings), and the full-pipeline contract suppresses
checks inside stages that have their own dedicated contract.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import jaxpr_walk as jw
from repro.analysis.report import Finding, PassResult

_F32_MAX = 3.4028235e38


@dataclasses.dataclass(frozen=True)
class Interval:
    lo: float
    hi: float

    def __post_init__(self):
        assert self.lo <= self.hi, (self.lo, self.hi)

    def hull(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))

    def __repr__(self):
        return f"[{self.lo}, {self.hi}]"


def _is_int(dtype) -> bool:
    return jnp.issubdtype(dtype, jnp.integer) or dtype == jnp.bool_


def dtype_interval(dtype) -> Interval:
    if dtype == jnp.bool_:
        return Interval(0, 1)
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return Interval(int(info.min), int(info.max))
    return Interval(-_F32_MAX, _F32_MAX)


def from_value(val) -> Interval:
    arr = np.asarray(val)
    if arr.size == 0:
        return Interval(0, 0)
    if arr.dtype == np.bool_:
        return Interval(int(arr.min()), int(arr.max()))
    if np.issubdtype(arr.dtype, np.integer):
        return Interval(int(arr.min()), int(arr.max()))
    return Interval(float(arr.min()), float(arr.max()))


def _corners(f, a: Interval, b: Interval) -> Interval:
    vals = []
    for x in (a.lo, a.hi):
        for y in (b.lo, b.hi):
            v = f(x, y)
            if isinstance(v, float) and math.isnan(v):
                return Interval(-math.inf, math.inf)
            vals.append(v)
    return Interval(min(vals), max(vals))


def _mono(f, a: Interval) -> Interval:
    lo, hi = f(a.lo), f(a.hi)
    return Interval(min(lo, hi), max(lo, hi))


def _shift_corners(f, a: Interval, s: Interval) -> Interval:
    slo = max(0, int(s.lo))
    shi = min(63, max(slo, int(s.hi)))
    vals = [f(int(x), y) for x in (a.lo, a.hi) for y in (slo, shi)]
    return Interval(min(vals), max(vals))


def _cmp(a: Interval, b: Interval, op: str) -> Interval:
    true_, false_ = Interval(1, 1), Interval(0, 0)
    if op in ("ge", "gt"):
        strict = op == "gt"
        if a.lo > b.hi or (not strict and a.lo >= b.hi):
            return true_
        if a.hi < b.lo or (strict and a.hi <= b.lo):
            return false_
    elif op in ("le", "lt"):
        strict = op == "lt"
        if a.hi < b.lo or (not strict and a.hi <= b.lo):
            return true_
        if a.lo > b.hi or (strict and a.lo >= b.hi):
            return false_
    elif op == "eq":
        if a.lo == a.hi == b.lo == b.hi:
            return true_
        if a.hi < b.lo or a.lo > b.hi:
            return false_
    elif op == "ne":
        if a.hi < b.lo or a.lo > b.hi:
            return true_
        if a.lo == a.hi == b.lo == b.hi:
            return false_
    return Interval(0, 1)


class _Ctx:
    """Shared per-analysis state: findings, options, dedup sets."""

    def __init__(self, findings, *, suppress_frames=(), check_fixed_mul=True,
                 label="", whitelist=()):
        self.findings = findings
        self.suppress_frames = frozenset(suppress_frames)
        self.check_fixed_mul = check_fixed_mul
        self.label = label
        self.whitelist = tuple(whitelist)   # (frame, primitive, reason)
        self._seen = set()
        self._suppressed_noted = set()
        self._cons_cache = {}

    def consumers(self, jaxpr):
        cons = self._cons_cache.get(id(jaxpr))
        if cons is None:
            cons = self._cons_cache[id(jaxpr)] = _consumer_map(jaxpr)
        return cons

    def once(self, key) -> bool:
        if key in self._seen:
            return False
        self._seen.add(key)
        return True

    def suppressed(self, eqn) -> bool:
        fns = jw.frame_functions(eqn)
        for f in fns:
            if f in self.suppress_frames:
                if f not in self._suppressed_noted:
                    self._suppressed_noted.add(f)
                    self.findings.append(Finding(
                        "info", "delegated",
                        f"{self.label}: checks inside {f!r} delegated to its "
                        "dedicated contract"))
                return True
        return False


def _consumer_map(jaxpr):
    """var id -> [(eqn, operand positions)] within one jaxpr level."""
    cons = {}
    for eqn in jaxpr.eqns:
        for i, v in enumerate(eqn.invars):
            if hasattr(v, "aval") and not hasattr(v, "val"):
                cons.setdefault(id(v), []).append((eqn, i))
    return cons


def _guarded_uses(var, jaxpr, ctx, depth=0) -> bool:
    """True when every (transitive) use of ``var`` is a ``select_n``
    choice lane (the saturating-guard idiom): the out-of-range value is
    statically dead — some predicate lane replaces it.  ``jnp.where``
    lowers to ``jit[name=_where]``, so uses are followed through
    call-like primitives into the jaxpr where the select lives."""
    uses = ctx.consumers(jaxpr).get(id(var), [])
    if not uses or depth > 4:
        return False
    for user, pos in uses:
        if user.primitive.name == "select_n" and pos > 0:
            continue
        sub = user.params.get("jaxpr", user.params.get("call_jaxpr"))
        if sub is None:
            return False
        subj = jw.closed_to_open(sub)
        if len(subj.invars) != len(user.invars):
            return False
        if not _guarded_uses(subj.invars[pos], subj, ctx, depth + 1):
            return False
    return True


def _check_int_result(ctx, eqn, raw: Interval, jaxpr) -> Interval:
    """Flag integer results escaping their dtype; return the clamped
    interval (what saturation — or the guarding select — would keep)."""
    dtype = eqn.outvars[0].aval.dtype
    if not jnp.issubdtype(dtype, jnp.integer):
        return raw
    rng = dtype_interval(dtype)
    if raw.lo >= rng.lo and raw.hi <= rng.hi:
        return raw
    clamped = Interval(max(raw.lo, rng.lo), min(raw.hi, rng.hi))
    if not ctx.suppressed(eqn):
        site = jw.user_site(eqn)
        desc = (f"{ctx.label}: {eqn.primitive.name} on {dtype} may reach "
                f"{raw} (range {rng})")
        wl_reason = None
        fns = jw.frame_functions(eqn)
        for frame, prim, reason in ctx.whitelist:
            if prim == eqn.primitive.name and frame in fns:
                wl_reason = reason
                break
        if _guarded_uses(eqn.outvars[0], jaxpr, ctx):
            if ctx.once(("guard", eqn.primitive.name, site)):
                ctx.findings.append(Finding(
                    "whitelisted", "guarded-overflow",
                    desc + " — result only feeds saturating select lanes",
                    site))
        elif wl_reason is not None:
            if ctx.once(("wl", eqn.primitive.name, site)):
                ctx.findings.append(Finding(
                    "whitelisted", "known-safe-overflow",
                    desc + f" — {wl_reason}", site))
        elif ctx.once(("overflow", eqn.primitive.name, site)):
            ctx.findings.append(Finding(
                "violation", f"{dtype}-overflow",
                desc + " — unguarded: silently wraps", site))
    return clamped


def _precondition_check(ctx, eqn, operand: Interval):
    """The fixed_mul 24-bit-magnitude precondition, checked at its |.|."""
    one = 1 << 24
    if "fixed_mul" not in jw.frame_functions(eqn) or not ctx.check_fixed_mul:
        return
    if ctx.suppressed(eqn):
        return
    if operand.lo < -one or operand.hi > one:
        site = jw.user_site(eqn)
        if ctx.once(("precond", site)):
            ctx.findings.append(Finding(
                "violation", "fixed-mul-precondition",
                f"{ctx.label}: fixed_mul operand may reach {operand}; the "
                "12/12-limb product is only exact for |q| <= 2^24",
                site))


def _run(jaxpr, env, ctx):
    def read(v):
        if hasattr(v, "val"):                      # Literal
            return from_value(v.val)
        return env.get(id(v), dtype_interval(v.aval.dtype))

    def write(v, iv):
        env[id(v)] = iv

    for eqn in jaxpr.eqns:
        ins = [read(v) for v in eqn.invars]
        name = eqn.primitive.name
        out = None

        if name in ("add", "sub", "mul"):
            f = {"add": lambda x, y: x + y, "sub": lambda x, y: x - y,
                 "mul": lambda x, y: x * y}[name]
            out = _corners(f, ins[0], ins[1])
            out = _check_int_result(ctx, eqn, out, jaxpr)
        elif name == "div":
            a, b = ins
            if b.lo <= 0 <= b.hi:
                out = Interval(-math.inf, math.inf)
            else:
                out = _corners(lambda x, y: x / y, a, b)
        elif name == "neg":
            out = Interval(-ins[0].hi, -ins[0].lo)
        elif name == "abs":
            a = ins[0]
            out = Interval(0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi)),
                           max(abs(a.lo), abs(a.hi)))
            _precondition_check(ctx, eqn, a)
        elif name == "sign":
            a = ins[0]
            out = Interval(-1 if a.lo < 0 else (0 if a.lo == 0 else 1),
                           1 if a.hi > 0 else (0 if a.hi == 0 else -1))
        elif name == "max":
            out = Interval(max(ins[0].lo, ins[1].lo), max(ins[0].hi, ins[1].hi))
        elif name == "min":
            out = Interval(min(ins[0].lo, ins[1].lo), min(ins[0].hi, ins[1].hi))
        elif name == "clamp":                       # lax.clamp(min, x, max)
            mn, x, mx = ins
            out = Interval(max(mn.lo, min(x.lo, mx.hi)),
                           max(mn.hi, min(x.hi, mx.hi)))
        elif name == "shift_left":
            out = _shift_corners(lambda a, s: a << s, ins[0], ins[1])
            out = _check_int_result(ctx, eqn, out, jaxpr)
        elif name in ("shift_right_arithmetic", "shift_right_logical"):
            a = ins[0]
            if name == "shift_right_logical" and a.lo < 0:
                out = dtype_interval(eqn.outvars[0].aval.dtype)
            else:
                out = _shift_corners(lambda x, s: x >> s, a, ins[1])
        elif name in ("and", "or", "xor"):
            dtype = eqn.outvars[0].aval.dtype
            if dtype == jnp.bool_:
                out = Interval(0, 1)
            elif all(i.lo >= 0 for i in ins):
                if name == "and":
                    out = Interval(0, min(i.hi for i in ins))
                else:
                    bits = max(int(i.hi).bit_length() for i in ins)
                    out = Interval(0, (1 << bits) - 1)
            else:
                out = dtype_interval(dtype)
        elif name == "not":
            out = (Interval(0, 1) if eqn.outvars[0].aval.dtype == jnp.bool_
                   else dtype_interval(eqn.outvars[0].aval.dtype))
        elif name in ("ge", "gt", "le", "lt", "eq", "ne"):
            out = _cmp(ins[0], ins[1], name)
        elif name == "select_n":
            pred, cases = ins[0], ins[1:]
            if pred.lo == pred.hi and 0 <= int(pred.lo) < len(cases):
                out = cases[int(pred.lo)]
            else:
                out = cases[0]
                for c in cases[1:]:
                    out = out.hull(c)
        elif name == "convert_element_type":
            dtype = eqn.outvars[0].aval.dtype
            a = ins[0]
            if _is_int(dtype):
                rng = dtype_interval(dtype)
                # XLA's float->int convert clamps at the type edges on the
                # backends we run; int->narrower-int wraps, so widen.
                lo = rng.lo if a.lo == -math.inf else int(math.floor(a.lo))
                hi = rng.hi if a.hi == math.inf else int(math.ceil(a.hi))
                if jnp.issubdtype(eqn.invars[0].aval.dtype, jnp.integer) \
                        and (lo < rng.lo or hi > rng.hi):
                    out = rng
                else:
                    out = Interval(max(lo, rng.lo), min(hi, rng.hi))
            else:
                out = Interval(float(a.lo), float(a.hi))
        elif name in ("reduce_max", "reduce_min", "reduce_and", "reduce_or",
                      "cumsum", "cummax"):
            out = ins[0]
            if name == "cumsum":
                n = int(eqn.invars[0].aval.size)
                out = _corners(lambda x, y: x * y, ins[0], Interval(1, n))
                out = _check_int_result(ctx, eqn, out, jaxpr)
        elif name == "reduce_sum":
            n = max(1, int(eqn.invars[0].aval.size) //
                    max(1, int(eqn.outvars[0].aval.size)))
            a = ins[0]
            out = Interval(min(a.lo * n, a.lo), max(a.hi * n, a.hi))
            out = _check_int_result(ctx, eqn, out, jaxpr)
        elif name == "dot_general":
            ((lc, _), _) = eqn.params["dimension_numbers"]
            k = 1
            for ax in lc:
                k *= int(eqn.invars[0].aval.shape[ax])
            prod = _corners(lambda x, y: x * y, ins[0], ins[1])
            out = Interval(min(prod.lo * k, prod.lo), max(prod.hi * k, prod.hi))
            out = _check_int_result(ctx, eqn, out, jaxpr)
        elif name in ("gather", "dynamic_slice", "slice", "rev", "copy",
                      "broadcast_in_dim", "reshape", "transpose", "squeeze",
                      "expand_dims", "device_put", "stop_gradient",
                      "reduce_precision"):
            out = ins[0]
        elif name == "concatenate":
            out = ins[0]
            for i in ins[1:]:
                out = out.hull(i)
        elif name == "pad":
            out = ins[0].hull(ins[1])
        elif name == "iota":
            d = int(eqn.params.get("dimension", 0))
            size = int(eqn.outvars[0].aval.shape[d]) if \
                eqn.outvars[0].aval.shape else 1
            out = Interval(0, max(0, size - 1))
        elif name == "optimization_barrier":
            for v, iv in zip(eqn.outvars, ins):
                write(v, iv)
            continue
        elif name in ("floor", "ceil", "round"):
            out = Interval(math.floor(ins[0].lo), math.ceil(ins[0].hi))
        elif name in ("exp", "exp2", "log", "log2", "tanh", "logistic",
                      "rsqrt", "sqrt", "erf", "sin", "cos", "integer_pow",
                      "pow", "is_finite"):
            out = _elementwise_math(name, eqn, ins)
        elif name in ("jit", "closed_call", "custom_vjp_call_jaxpr",
                      "custom_jvp_call", "custom_vjp_call", "remat",
                      "checkpoint", "core_call"):
            sub = None
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                if key in eqn.params:
                    sub = eqn.params[key]
                    break
            if sub is None:
                subs = list(jw.sub_jaxprs(eqn))
                sub = subs[0] if subs else None
            outs = _run_sub(sub, ins, eqn, ctx) if sub is not None else None
            if outs is not None:
                for v, iv in zip(eqn.outvars, outs):
                    write(v, iv)
                continue
        # fall through: unknown / unhandled primitive
        if out is None:
            if ctx.once(("widen", name)):
                ctx.findings.append(Finding(
                    "info", "widened",
                    f"{ctx.label}: no transfer function for primitive "
                    f"{name!r}; result widened to its dtype range"))
            for v in eqn.outvars:
                write(v, dtype_interval(v.aval.dtype))
            continue
        write(eqn.outvars[0], out)
        for v in eqn.outvars[1:]:
            write(v, dtype_interval(v.aval.dtype))

    return [read(v) for v in jaxpr.outvars]


def _elementwise_math(name, eqn, ins):
    a = ins[0]
    fns = {
        "exp": lambda x: math.exp(min(x, 700.0)),
        "exp2": lambda x: 2.0 ** min(x, 1000.0),
        "log": lambda x: math.log(x) if x > 0 else -math.inf,
        "log2": lambda x: math.log2(x) if x > 0 else -math.inf,
        "tanh": math.tanh,
        "logistic": lambda x: 1.0 / (1.0 + math.exp(-max(min(x, 700), -700))),
        "erf": math.erf,
        "sqrt": lambda x: math.sqrt(max(x, 0.0)),
        "rsqrt": lambda x: (1.0 / math.sqrt(x)) if x > 0 else math.inf,
        "is_finite": None, "sin": None, "cos": None,
        "integer_pow": None, "pow": None,
    }
    if name in ("sin", "cos"):
        return Interval(-1.0, 1.0)
    if name == "is_finite":
        return Interval(0, 1)
    if name == "integer_pow":
        y = int(eqn.params["y"])
        vals = [x ** y for x in (a.lo, a.hi)]
        if y % 2 == 0 and a.lo <= 0 <= a.hi:
            vals.append(0)
        return Interval(min(vals), max(vals))
    if name == "pow":
        return _corners(lambda x, y: x ** y if x > 0 else 0.0, a, ins[1])
    return _mono(fns[name], a)


def _run_sub(sub, ins, eqn, ctx):
    """Interpret a nested (Closed)Jaxpr, mapping operand intervals in."""
    consts = list(getattr(sub, "consts", ()) or ())
    jaxpr = jw.closed_to_open(sub)
    env = {}
    for v, c in zip(jaxpr.constvars, consts):
        env[id(v)] = from_value(c)
    if len(jaxpr.invars) == len(ins):
        mapped = ins
    else:
        # operand packing we don't model (scan carries etc.): widen.
        mapped = [dtype_interval(v.aval.dtype) for v in jaxpr.invars]
    for v, iv in zip(jaxpr.invars, mapped):
        env[id(v)] = iv
    outs = _run(jaxpr, env, ctx)
    if len(outs) != len(eqn.outvars):
        return None
    return outs


def analyze_fn(fn, example_args, input_intervals, *, label="fn",
               suppress_frames=(), check_fixed_mul=True, whitelist=()):
    """Interval-analyze ``fn`` traced at ``example_args``.

    ``input_intervals``: one Interval per flattened input leaf (None
    entries default to the leaf dtype's full range).  Returns
    ``(findings, out_intervals)``.
    """
    findings = []
    ctx = _Ctx(findings, suppress_frames=suppress_frames,
               check_fixed_mul=check_fixed_mul, label=label,
               whitelist=whitelist)
    closed = jax.make_jaxpr(fn)(*example_args)
    jaxpr = closed.jaxpr
    env = {}
    for v, c in zip(jaxpr.constvars, closed.consts):
        env[id(v)] = from_value(c)
    leaves = jax.tree.leaves(example_args)
    ivs = list(input_intervals) + [None] * (len(leaves) - len(input_intervals))
    for v, leaf, iv in zip(jaxpr.invars, leaves, ivs):
        env[id(v)] = iv if iv is not None else dtype_interval(v.aval.dtype)
    outs = _run(jaxpr, env, ctx)
    return findings, outs


# ---------------------------------------------------------------------------
# Engine-level contracts
# ---------------------------------------------------------------------------

def _assume(findings, label, text):
    findings.append(Finding("assumption", "domain-fact", f"{label}: {text}"))


def check_ranges(engine, x) -> PassResult:
    """Run the Q8.24 contracts selected by the engine's execution modes."""
    from repro.core import approx, fixedpoint as fxp, lut as lutlib

    cfg = engine.exec_cfg
    findings = []
    metrics = {}
    one = 1 << fxp.FRAC_BITS
    fixed_modes = ("lut_fixed", "pallas")
    if cfg.softmax_mode not in fixed_modes and cfg.act_approx == "exact":
        findings.append(Finding(
            "info", "scope", "plan uses no fixed-point pipelines; nothing "
            "to range-check"))
        return PassResult("ranges", findings, metrics)
    if cfg.softmax_mode == "pallas":
        findings.append(Finding(
            "info", "scope",
            "pallas kernels execute the same Q8.24 ops tile-by-tile; "
            "contracts verify the jnp reference pipeline the kernels are "
            "bit-checked against (tests/test_kernels.py)"))

    if cfg.family == "kwt":
        from repro.models import kwt
        k_lens = [kwt.seqlen(cfg)]
    else:
        k_lens = [int(x.shape[-1])] if hasattr(x, "shape") and x.ndim else [64]

    if cfg.softmax_mode in fixed_modes:
        for k in k_lens:
            pre = max(0, int(np.ceil(np.log2(max(k, 1)))) - 6)
            label = f"softmax_q824[K={k}]"
            # (1) full pipeline; reciprocal + product have own contracts
            f1, _ = analyze_fn(
                lambda v: approx.softmax(v, mode="lut_fixed"),
                (jnp.zeros((1, k)),), [None], label=label,
                suppress_frames=("reciprocal_q24", "fixed_mul"))
            findings += f1
            # (2) reciprocal stage under the dominant-lane row-sum bound
            _assume(findings, label,
                    f"row sum s_q >= 2^(24-pre)={1 << (24 - pre)} (the "
                    "max-normalised row always has a z=0 lane at e^0=1)")
            bank = lutlib.make_lut_bank()
            f2, _ = analyze_fn(
                lambda s: lutlib.reciprocal_q24(s, bank),
                (jnp.zeros((1, 1), jnp.int32),),
                [Interval(one >> pre, k * (one >> pre))],
                label=f"{label}/reciprocal",
                whitelist=((
                    "reciprocal_q24", "shift_left",
                    "mantissa normalisation (s>>tp)<<tn: tp/tn are "
                    "magnitude-correlated with s (ilog2), so the result "
                    "is in [1,2) Q8.24 — invisible to intervals"),))
            findings += f2
            # (3) the normalisation product's exactness precondition
            _assume(findings, label,
                    "1/s <= 2^pre in Q8.24 (s >= 2^-pre real), so the "
                    "post-shift reciprocal magnitude is <= 1.0")
            f3, _ = analyze_fn(
                fxp.fixed_mul,
                (jnp.zeros((1, k), jnp.int32), jnp.zeros((1, 1), jnp.int32)),
                [Interval(0, one), Interval(0, one)],
                label=f"{label}/normalise")
            findings += f3
        metrics["softmax_contracts"] = 3 * len(k_lens)

    if cfg.act_approx in ("lut", "pallas") and cfg.activation == "gelu":
        f4, _ = analyze_fn(
            lambda v: approx.gelu(v, mode="lut"),
            (jnp.zeros((1, max(k_lens))),), [None], label="gelu_lut")
        findings += f4
        metrics["gelu_contracts"] = 1

    # (4) the power-of-2 rescale primitive at the recipe's input gain —
    # the exact site the PR-6 satellite fix saturates.
    shift = engine.recipe.input_exponent if engine.recipe else 5
    envelope = 8.0
    _assume(findings, "po2_rescale",
            f"normalised activations |x| <= {envelope} entering the input "
            f"gain 2^{shift} (post-LayerNorm envelope)")
    f5, _ = analyze_fn(
        lambda v: fxp.fixed_shift_mul(fxp.to_fixed(v), shift),
        (jnp.zeros((4,)),), [Interval(-envelope, envelope)],
        label="po2_rescale")
    findings += f5
    metrics["violations"] = sum(
        1 for f in findings if f.severity == "violation")
    return PassResult("ranges", findings, metrics)
