"""Dtype-residency lint: prove (or refute) ``Backend.int_resident``.

The Engine claims its lut/pallas plans keep quantised weights in stored
integer form.  This pass checks the claim at the jaxpr level instead of
by example: it walks the traced programs, propagates a taint set from the
integer weight-storage inputs (the packed QTensor leaves), and reports
every ``convert_element_type`` to float that is reachable from them.

Two programs are analysed per integer-resident plan:

  * the **unpack stage** (``Engine.live_params``'s jitted
    ``quant.dequantize_tree``) — the separate executable a
    non-executing resident Engine runs per call.  Every int->float cast
    here is the PR-5 "hidden unpack" leak: the weights are
    integer-*resident* but the model still consumes a float view.
    These are whitelisted with a report line and counted as
    ``float_leak_count``.  Integer-EXECUTING plans (``engine.int_exec``)
    have no unpack stage at all, so the count is zero by construction —
    the ROADMAP "full-integer execution" criterion.

  * the **in-module resident program** (the model forward traced directly
    on the packed tree — the path integer-executing plans and fused-jit
    drivers take).  Sanctioned casts are classified by their trace-time
    call stack:

      - frames through ``quant.resident_values`` — the po2 weight
        de-scale epilogue (exact, fusion-isolated); whitelisted.
      - frames through ``quant.int_container`` — value-preserving
        int->f32 container move for exact integer GEMM (the f32
        mantissa holds the int8 grid exactly); whitelisted.
      - frames through ``quant.requant`` / ``kernels.ops.int8_matmul``
        — the per-channel po2 requant epilogue on an integer
        accumulator; whitelisted.
      - frames through ``quant.gather_descale`` — row-wise embedding
        descale (only looked-up rows leave integer form); whitelisted.
      - frames through ``fixedpoint.to_float`` — the Q8.24 pipeline's
        exit boundary (the jnp reference's emulation of the device's
        ALU_TO_FLOAT instruction); whitelisted.

    Anything else tainted that converts an integer to a float is a
    **violation**: an unsanctioned dequantisation snuck into the plan.

**Strict mode** (``check_residency(..., strict=True)``, CLI
``python -m repro.analysis --strict``) asserts the FULL-integer claim:
the plan must be integer-executing, ``float_leak_count`` must be zero,
and whole-tensor weight descales feeding float einsums
(``quant.qt_einsum``'s float view) are violations even though plain
resident mode sanctions them — the only sanctioned float views left are
the additive-consumption leaves (positional tables) and the requant /
container / gather epilogues above.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from repro.analysis import jaxpr_walk as jw
from repro.analysis.report import Finding, PassResult

# Trace-time frame names that sanction an int->float cast (innermost-wins
# classification below reports which rule fired).
_WHITELIST = (
    ("resident_values", "weight-descale",
     "po2 de-scale epilogue (exact, fusion-isolated)"),
    ("int_container", "int-container",
     "value-preserving int->f32 container move (exact integer GEMM)"),
    ("int8_matmul", "requant-epilogue",
     "per-channel po2 requant of the kernel's integer accumulator"),
    ("requant", "requant-epilogue",
     "per-channel po2 requant of the integer accumulator"),
    ("gather_descale", "gather-descale",
     "row-wise embedding descale (looked-up rows only)"),
    ("to_float", "q824-boundary",
     "Q8.24 pipeline exit (ALU_TO_FLOAT reference)"),
)


def _is_int(aval) -> bool:
    return jnp.issubdtype(aval.dtype, jnp.integer)


def _is_float(aval) -> bool:
    return jnp.issubdtype(aval.dtype, jnp.floating)


def _var_key(v):
    return id(v)


def _tainted_float_casts(jaxpr, taint_in, hits, depth=0):
    """Walk ``jaxpr`` propagating taint; append (eqn, in_aval) for every
    int->float convert_element_type whose operand is tainted."""
    tainted = set()
    for v, t in zip(jaxpr.invars, taint_in):
        if t:
            tainted.add(_var_key(v))

    for eqn in jaxpr.eqns:
        in_taint = [(_var_key(v) in tainted) if hasattr(v, "aval") and
                    not isinstance(v, jex_core.Literal) else False
                    for v in eqn.invars]
        any_taint = any(in_taint)
        if (eqn.primitive.name == "convert_element_type" and in_taint[0]
                and _is_int(eqn.invars[0].aval)
                and _is_float(eqn.outvars[0].aval)):
            hits.append(eqn)
        for sub in jw.sub_jaxprs(eqn):
            if len(sub.invars) == len(eqn.invars):
                sub_taint = in_taint
            else:
                # scan/cond-style operand packing: conservative — taint
                # every inner input if any outer operand is tainted.
                sub_taint = [any_taint] * len(sub.invars)
            _tainted_float_casts(sub, sub_taint, hits, depth + 1)
        if any_taint:
            for v in eqn.outvars:
                tainted.add(_var_key(v))


def _classify(eqn):
    fns = jw.frame_functions(eqn)
    for fn, kind, why in _WHITELIST:
        if fn in fns:
            return kind, why
    return None, None


def _collect(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    leaves = jax.tree.leaves(args)
    taint = [hasattr(leaf, "dtype") and
             jnp.issubdtype(leaf.dtype, jnp.integer) for leaf in leaves]
    hits = []
    _tainted_float_casts(jaxpr.jaxpr, taint, hits)
    return hits


def check_residency(engine, x, strict: bool = False) -> PassResult:
    """Residency lint over the plan's forward program(s) at input ``x``.

    ``strict=True`` asserts the full-integer claim (see module
    docstring): non-executing plans and whole-tensor float weight views
    become violations, and ``float_leak_count`` must be zero."""
    from repro.core import quant

    findings = []
    metrics = {"float_leak_count": 0, "descale_sites": 0}
    claims = engine.backend.int_resident
    holds = engine.int_resident
    if claims and not holds:
        findings.append(Finding(
            "warning", "residency-claim",
            f"backend {engine.backend_name!r} registers int_resident but the "
            "deployed tree holds no stored-integer leaves (family "
            f"{engine.exec_cfg.family!r} falls back to dequantise-first)"))
    if strict and not engine.int_exec:
        findings.append(Finding(
            "violation", "strict-mode",
            f"strict residency demands an integer-executing plan; "
            f"backend {engine.backend_name!r} planned "
            f"{'resident (dequantise-per-call)' if holds else 'float'} "
            "execution"))
    if not holds:
        findings.append(Finding(
            "info", "residency-claim",
            "plan deploys a float tree; no integer storage to leak"))
        return PassResult("residency", findings, metrics)

    if engine.int_exec:
        # Integer-executing plans run the model straight on the packed
        # tree: there is no per-call unpack stage to leak through, so
        # float_leak_count is zero by construction.
        findings.append(Finding(
            "info", "unpack-stage",
            "no unpack stage: the plan is integer-executing (the model "
            "consumes the packed tree directly)"))
    else:
        # (a) the separate unpack stage the Engine executes per call
        unpack_hits = _collect(quant.dequantize_tree, engine.params)
        metrics["float_leak_count"] = len(unpack_hits)
        findings.append(Finding(
            "whitelisted", "unpack-stage",
            f"{len(unpack_hits)} int->float cast(s) in the separate jitted "
            "unpack stage (Engine.live_params): the plan is integer-RESIDENT "
            "but not integer-EXECUTING — the per-call float materialisation "
            "the int-exec plan flavour eliminates"))

    # (b) the in-module resident program: forward on the packed tree
    cfg = engine.exec_cfg
    mod = engine._mod
    programs = [("forward", lambda p, xx: mod.forward(p, xx, cfg), x)]
    if cfg.family == "kwt":
        t = cfg.input_dim[1]
        frames = jnp.zeros((x.shape[0], t, cfg.input_dim[0]), jnp.float32)
        window = jnp.zeros((x.shape[0], t, cfg.d_model), jnp.float32)
        programs += [
            ("embed_frames", lambda p, fr: mod.embed_frames(p, fr, cfg),
             frames),
            ("encode_window", lambda p, w: mod.encode_window(p, w, cfg),
             window),
        ]
    for prog_name, fn, arg in programs:
        for eqn in _collect(fn, engine.params, arg):
            kind, why = _classify(eqn)
            if (strict and kind == "weight-descale"
                    and "qt_einsum" in jw.frame_functions(eqn)):
                # A whole-tensor descale feeding a float einsum: the
                # qt_einsum fallback path.  Plain resident mode sanctions
                # it; under the full-integer claim it is a leak (only
                # additive-consumption descales, e.g. positional tables,
                # stay whitelisted).
                kind = None
            src, dst = eqn.invars[0].aval, eqn.outvars[0].aval
            desc = (f"{prog_name}: {src.dtype}{list(src.shape)} -> "
                    f"{dst.dtype}")
            if kind == "weight-descale":
                metrics["descale_sites"] += 1
                findings.append(Finding("whitelisted", kind,
                                        f"{desc} — {why}", jw.user_site(eqn)))
            elif kind is not None:
                findings.append(Finding("whitelisted", kind,
                                        f"{desc} — {why}", jw.user_site(eqn)))
            else:
                findings.append(Finding(
                    "violation", "float-leak",
                    f"{desc}: unsanctioned dequantisation reachable from "
                    "packed weight storage", jw.user_site(eqn)))
    return PassResult("residency", findings, metrics)
