"""RAM-budget checker: does the plan fit the paper's 64 kB target?

The paper deploys KWT-Tiny on a bare-metal RISC-V board with 64 kB of
RAM; the whole point of int8 ROM + 2.69 kB LUT bank + Q8.24 activations
is staying inside it.  This pass computes the static footprint of an
Engine plan:

    total = deployed parameter bytes   (packed ints + residual floats)
          + LUT bank ROM bytes
          + peak activation live-set   (buffer liveness over the jaxpr)

The live-set walks the forward program's equations in order, allocating
each output buffer at its defining equation and freeing each operand
after its last use — the high-water mark is what a bump allocator (or
the board's static arena) must provision.  Weight leaves are excluded
from the live-set (already counted as parameter bytes); the input buffer
counts.

The 64 kB gate applies to the paper's deployment target (the kwt-tiny
config); other configs get the same table as information — kwt_1 at
~607k params is a desktop model and is *reported* against the budget,
not failed.
"""

from __future__ import annotations

import jax

from repro.analysis import jaxpr_walk as jw
from repro.analysis.report import Finding, PassResult

PAPER_BUDGET_BYTES = 64 * 1024

# Config names gated (not just reported) against the paper budget.
_GATED_CONFIGS = ("kwt-tiny",)


def _peak_live(jaxpr, count_invar, ctx_bytes=0):
    """High-water-mark live bytes over one jaxpr's equations.

    ``count_invar``: per-invar flags — weight operands are excluded (the
    caller counts them as parameter ROM).  Nested jaxprs (jit bodies,
    custom_vjp primals) are charged against the live set at their call
    site; their invars alias already-counted outer buffers, so only
    their internal temporaries add bytes.
    """
    last = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if hasattr(v, "aval") and not hasattr(v, "val"):
                last[id(v)] = i
    for v in jaxpr.outvars:
        if hasattr(v, "aval") and not hasattr(v, "val"):
            last[id(v)] = len(jaxpr.eqns)

    live = {}
    for v, counted in zip(jaxpr.invars, count_invar):
        if counted and id(v) in last:
            live[id(v)] = jw.aval_bytes(v.aval)
    for v in jaxpr.constvars:
        if id(v) in last:
            live[id(v)] = jw.aval_bytes(v.aval)

    peak = sum(live.values()) + ctx_bytes
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            if id(v) in last:
                live[id(v)] = jw.aval_bytes(v.aval)
        cur = sum(live.values()) + ctx_bytes
        for sub in jw.sub_jaxprs(eqn):
            peak = max(peak, _peak_live(
                sub, [False] * len(sub.invars), cur))
        peak = max(peak, cur)
        for v in eqn.invars:
            if id(v) in last and last[id(v)] == i:
                live.pop(id(v), None)
    return peak


def peak_activation_bytes(fn, params, x) -> int:
    """Peak live activation bytes of ``fn(params, x)`` traced at ``x``."""
    closed = jax.make_jaxpr(fn)(params, x)
    n_param = len(jax.tree.leaves(params))
    n_in = len(closed.jaxpr.invars)
    flags = [False] * n_param + [True] * (n_in - n_param)
    return _peak_live(closed.jaxpr, flags)


def check_budget(engine, x, budget: int | None = None) -> PassResult:
    """Static RAM table for the plan; gated for the paper's target config."""
    findings = []
    cfg = engine.exec_cfg
    gated = budget is not None or cfg.name in _GATED_CONFIGS
    cap = PAPER_BUDGET_BYTES if budget is None else budget
    if gated and budget is None and engine.backend.uses_kernels:
        # Pallas plans stage pad_to_block tile buffers + whole-table VMEM
        # operands — TPU working memory, not board RAM.  The 64 kB gate
        # models the bare-metal C deployment, which maps to the kernel-
        # free (lut) plan; kernel plans get the table informationally.
        gated = False
        findings.append(Finding(
            "info", "ram-budget-scope",
            f"backend {engine.backend_name!r} stages Pallas tile buffers "
            "(TPU VMEM, not board RAM); the 64 kB gate is enforced on the "
            "kernel-free deployment plan — table reported informationally"))

    act = peak_activation_bytes(
        lambda p, xx: engine._mod.forward(p, xx, cfg), engine.params, x)
    rom = engine.rom_bytes
    lut = engine.lut_bytes
    residual = engine.param_bytes - rom
    total = engine.param_bytes + lut + act

    metrics = {
        "rom_bytes": rom, "lut_bytes": lut,
        "residual_float_bytes": residual,
        "peak_activation_bytes": act,
        "total_bytes": total,
        "budget_bytes": cap if gated else 0,
    }
    shape = list(getattr(x, "shape", ()))
    findings.append(Finding(
        "info", "ram-table",
        f"{cfg.name}/{engine.backend_name} @ input {shape}: "
        f"rom {rom} B + residual {residual} B + lut {lut} B + "
        f"activations {act} B = {total} B"))
    if gated:
        if total > cap:
            findings.append(Finding(
                "violation", "ram-budget",
                f"{total} B exceeds the {cap} B deployment budget "
                f"(over by {total - cap} B)"))
        else:
            findings.append(Finding(
                "info", "ram-budget",
                f"fits the {cap} B target with {cap - total} B headroom"))
    else:
        findings.append(Finding(
            "info", "ram-budget",
            f"{PAPER_BUDGET_BYTES} B gate not enforced for this plan; "
            f"informationally it {'is OVER' if total > PAPER_BUDGET_BYTES else 'fits'}"))
    return PassResult("budget", findings, metrics)
