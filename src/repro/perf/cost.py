"""Static cost model: FLOPs / bytes moved per named stage of an Engine plan.

The paper's headline result is a cost ledger — per-op clock cycles
(Figs 3-5, Table IX) pinning GELU/SoftMax as the 26M-cycle inference's
hot spots and auditing the 5x win down to 5.5M cycles.  This module is
the repo's analogue at jaxpr granularity: it walks any compiled Engine
program with the same traversal machinery as ``repro.analysis`` and
accumulates, per equation,

* **flops** — 2*M*N*K for ``dot_general``/``conv``, output size for
  element-wise math, input size for reductions, ``5*n*log2(n)`` for FFT
  stages; layout ops (reshape/transpose/broadcast) are free;
* **bytes moved** — operand + result buffer bytes of every
  compute-bearing or data-moving equation (a flat-memory traffic model:
  each operand is read once, each result written once; layout-only ops
  move nothing — XLA folds them into consumers);
* **arithmetic intensity** — flops / bytes, the roofline x-axis.

Each equation is attributed to a **stage** (``unpack`` / ``featurise``
/ ``embed`` / ``encode`` / ``detector`` — from the trace-time user
frames, the same provenance the residency pass keys whitelists on) and
an **op class** (``matmul`` / ``softmax`` / ``gelu`` / ``norm`` /
``fft`` / ``other``), so the table reads like the paper's: one row per
(stage, op), with an estimated-cycles column once a
:class:`repro.perf.roofline.MachineModel` prices it.

Call-like primitives are handled with multipliers: ``scan`` bodies
count ``length`` times, ``pallas_call`` kernels count once per grid
step over their *block-shaped* body (so Pallas padding shows up as real
extra work — which it is), ``cond`` contributes its most expensive
branch, ``while`` bodies count once (flagged in ``notes``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax

from repro.analysis import jaxpr_walk as jw

# -- equation classification ------------------------------------------------

# op class by trace-time frame function name (innermost frame wins)
_OP_BY_FUNC = {
    "softmax": ("softmax_exact", "softmax_lut", "fixed_softmax",
                "masked_softmax", "softmax", "pre_shift", "lut_softmax",
                "_softmax_kernel"),
    "gelu": ("gelu_exact", "gelu_lut", "gelu", "lut_gelu", "silu",
             "sigmoid_lut", "softplus", "sqrelu", "_gelu_kernel",
             "activation"),
    "norm": ("apply_norm", "_rms"),
    "fft": ("_frame_features", "mfcc"),
    # integer-execution epilogue/prologue work (quant.int_exec_einsum):
    # activation quantise, container moves, per-channel requant, row
    # gather-descale — everything around the integer GEMM itself (the
    # dot_general still classifies as matmul by primitive fallback)
    "requant": ("quantize_act", "requant", "int_container",
                "gather_descale"),
    # dispatch-trivial contractions that int_exec_einsum unrolls into an
    # elementwise multiply-add chain (quant.matmul_unrolled): still the
    # linear algebra, priced as MACs in _walk so matmul_flops stays
    # backend-invariant (2*M*N*K, the dot_general convention)
    "matmul": ("matmul_unrolled",),
}

# stage by frame function name, scanned innermost -> outermost
_STAGE_BY_FUNC = {
    "embed_frames": "embed",
    "encode_window": "encode",
    "dequantize_tree": "unpack",
    "dequantize": "unpack",
    "unpack_po2": "unpack",
    "unpack_payload": "unpack",
}

# stage by the repo file a frame lives in (used when no function matches)
_STAGE_BY_FILE = {
    "features.py": "featurise",
    "detector.py": "detector",
}

# layout/metadata primitives: no flops, no modelled memory traffic (XLA
# folds them into their consumers; counting them would double-charge)
_FREE_PRIMS = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "bitcast_convert_type", "stop_gradient", "optimization_barrier",
    "copy", "iota", "slice", "rev", "split",
})

# one flop per output element
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs", "sign",
    "exp", "exp2", "expm1", "log", "log1p", "tanh", "sin", "cos", "erf",
    "erfc", "erf_inv", "rsqrt", "sqrt", "cbrt", "logistic", "pow",
    "integer_pow", "floor", "ceil", "round", "clamp", "nextafter",
    "select_n", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "and", "or", "xor", "not", "eq", "ne", "lt",
    "le", "gt", "ge", "is_finite", "add_any", "exp2_p",
})

# one flop per *input* element (reductions)
_REDUCTIONS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cumprod", "cummax",
    "cummin", "reduce_precision", "logsumexp",
})


def _out_avals(eqn):
    return [v.aval for v in eqn.outvars if hasattr(v, "aval")]


def _in_avals(eqn):
    return [v.aval for v in eqn.invars if hasattr(v, "aval")]


def _size(avals) -> float:
    return float(sum(int(a.size) for a in avals))


def eqn_flops(eqn) -> float:
    """Modelled floating(/integer)-op count of one equation."""
    prim = eqn.primitive.name
    if prim in _FREE_PRIMS:
        return 0.0
    if prim == "dot_general":
        (lc, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        k = 1
        for ax in lc:
            k *= int(lhs.shape[ax])
        return 2.0 * _size(_out_avals(eqn)[:1]) * k
    if prim == "conv_general_dilated":
        rhs = eqn.invars[1].aval
        out = _out_avals(eqn)[0]
        dn = eqn.params["dimension_numbers"]
        k = int(rhs.size) // int(rhs.shape[dn.rhs_spec[0]])
        return 2.0 * float(out.size) * k
    if prim == "fft":
        n = int(eqn.invars[0].aval.shape[-1])
        batch = _size(_in_avals(eqn)[:1]) / max(n, 1)
        return 5.0 * batch * n * max(math.log2(max(n, 2)), 1.0)
    if prim in _ELEMENTWISE:
        return _size(_out_avals(eqn)[:1])
    if prim in _REDUCTIONS:
        return _size(_in_avals(eqn)[:1])
    return 0.0


def eqn_bytes(eqn) -> float:
    """Modelled memory traffic of one equation (operands read + results
    written once; layout-only primitives move nothing)."""
    if eqn.primitive.name in _FREE_PRIMS:
        return 0.0
    return float(sum(jw.aval_bytes(a) for a in _in_avals(eqn))
                 + sum(jw.aval_bytes(a) for a in _out_avals(eqn)))


def classify(eqn, default_stage: str) -> tuple[str, str]:
    """(stage, op) attribution of one equation from its user frames."""
    frames = jw.user_frames(eqn)
    op = None
    stage = None
    for i, f in enumerate(frames):
        fn = f.function_name
        fname = f.file_name.rsplit("/", 1)[-1]
        if op is None:
            for label, funcs in _OP_BY_FUNC.items():
                if fn in funcs:
                    op = label
                    break
        if stage is None:
            stage = _STAGE_BY_FUNC.get(fn)
            if stage is None and i == 0:
                stage = _STAGE_BY_FILE.get(fname)
    if op is None:
        op = "matmul" if eqn.primitive.name in (
            "dot_general", "conv_general_dilated") else "other"
    return stage or default_stage, op


# -- accumulation -----------------------------------------------------------

@dataclasses.dataclass
class CostLine:
    """Accumulated cost of one (stage, op) cell of the table."""

    stage: str
    op: str
    flops: float = 0.0
    bytes: float = 0.0
    eqns: int = 0

    @property
    def intensity(self) -> float:
        return self.flops / self.bytes if self.bytes else 0.0


@dataclasses.dataclass
class CostReport:
    """Per-(stage, op) cost lines of one (or several merged) programs."""

    lines: dict = dataclasses.field(default_factory=dict)
    notes: list = dataclasses.field(default_factory=list)

    def add(self, stage: str, op: str, flops: float, bytes_: float,
            mult: float = 1.0) -> None:
        line = self.lines.get((stage, op))
        if line is None:
            line = self.lines[(stage, op)] = CostLine(stage, op)
        line.flops += mult * flops
        line.bytes += mult * bytes_
        line.eqns += 1

    def merge(self, other: "CostReport") -> "CostReport":
        for (stage, op), line in other.lines.items():
            cur = self.lines.get((stage, op))
            if cur is None:
                self.lines[(stage, op)] = dataclasses.replace(line)
            else:
                cur.flops += line.flops
                cur.bytes += line.bytes
                cur.eqns += line.eqns
        self.notes.extend(other.notes)
        return self

    # -- totals -----------------------------------------------------------

    @property
    def flops(self) -> float:
        return sum(ln.flops for ln in self.lines.values())

    @property
    def bytes(self) -> float:
        return sum(ln.bytes for ln in self.lines.values())

    @property
    def matmul_flops(self) -> float:
        """dot/conv flops only — backend-invariant for identical math
        (the LUT/Pallas backends change softmax/GELU realisation, never
        the linear algebra; tests/test_perf.py pins this)."""
        return sum(ln.flops for ln in self.lines.values()
                   if ln.op == "matmul")

    @property
    def intensity(self) -> float:
        return self.flops / self.bytes if self.bytes else 0.0

    def by_stage(self) -> dict:
        out: dict = {}
        for ln in self.lines.values():
            cur = out.setdefault(ln.stage, CostLine(ln.stage, "*"))
            cur.flops += ln.flops
            cur.bytes += ln.bytes
            cur.eqns += ln.eqns
        return out

    def stage_weights(self, machine=None) -> dict:
        """Relative time share per stage (flight-recorder attribution):
        modelled stage time on ``machine`` (roofline max of compute and
        memory terms), normalised to sum to 1; flops share if no machine."""
        stages = self.by_stage()
        if machine is None:
            tot = sum(ln.flops for ln in stages.values()) or 1.0
            return {s: ln.flops / tot for s, ln in stages.items()}
        t = {s: machine.time_s(ln.flops, ln.bytes)
             for s, ln in stages.items()}
        tot = sum(t.values()) or 1.0
        return {s: v / tot for s, v in t.items()}

    # -- rendering --------------------------------------------------------

    def rows(self, machine=None) -> list[dict]:
        """Table rows (dicts), paper-style: one per (stage, op) plus an
        estimated-cycles column when a MachineModel prices the plan."""
        out = []
        for (stage, op) in sorted(self.lines):
            ln = self.lines[(stage, op)]
            row = {"stage": stage, "op": op, "flops": round(ln.flops),
                   "bytes_moved": round(ln.bytes),
                   "arithmetic_intensity": round(ln.intensity, 4),
                   "eqns": ln.eqns}
            if machine is not None:
                row["est_cycles"] = round(machine.cycles(ln.flops, ln.bytes))
            out.append(row)
        return out

    def table(self, machine=None) -> str:
        cols = ["stage", "op", "flops", "bytes_moved",
                "arithmetic_intensity", "eqns"]
        if machine is not None:
            cols.append("est_cycles")
        rows = self.rows(machine)
        head = "| " + " | ".join(cols) + " |"
        sep = "|" + "|".join("---" for _ in cols) + "|"
        body = ["| " + " | ".join(str(r[c]) for c in cols) + " |"
                for r in rows]
        total = {"stage": "**total**", "op": "", "flops": round(self.flops),
                 "bytes_moved": round(self.bytes),
                 "arithmetic_intensity": round(self.intensity, 4),
                 "eqns": sum(ln.eqns for ln in self.lines.values())}
        if machine is not None:
            total["est_cycles"] = round(machine.cycles(self.flops,
                                                       self.bytes))
        body.append("| " + " | ".join(str(total[c]) for c in cols) + " |")
        return "\n".join([head, sep] + body)

    def to_dict(self, machine=None) -> dict:
        return {"flops": round(self.flops),
                "bytes_moved": round(self.bytes),
                "matmul_flops": round(self.matmul_flops),
                "arithmetic_intensity": round(self.intensity, 4),
                "lines": self.rows(machine),
                "notes": list(self.notes)}


# -- jaxpr walking ----------------------------------------------------------

def _grid_size(eqn) -> float:
    gm = eqn.params.get("grid_mapping")
    grid = tuple(getattr(gm, "grid", ()) or ())
    n = 1.0
    for g in grid:
        try:
            n *= float(g)
        except TypeError:      # symbolic/dynamic grid dim: count once
            pass
    return n


def _branch_jaxprs(eqn):
    return [jw.closed_to_open(b) for b in eqn.params.get("branches", ())]


def _walk(jaxpr, mult: float, default_stage: str, rep: CostReport) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        subs = list(jw.sub_jaxprs(eqn))
        if subs:
            # call-like primitive: charge the nested program, not the call
            if prim == "cond":
                best = None
                for b in _branch_jaxprs(eqn):
                    sub_rep = CostReport()
                    _walk(b, mult, default_stage, sub_rep)
                    if best is None or sub_rep.flops > best.flops:
                        best = sub_rep
                if best is not None:
                    rep.merge(best)
                continue
            sub_mult = mult
            if prim == "scan":
                sub_mult = mult * float(eqn.params.get("length", 1))
            elif prim == "pallas_call":
                sub_mult = mult * _grid_size(eqn)
            elif prim == "while":
                rep.notes.append(
                    "while body counted once (static trip count unknown)")
            for sub in subs:
                _walk(sub, sub_mult, default_stage, rep)
            continue
        stage, op = classify(eqn, default_stage)
        flops = eqn_flops(eqn)
        if op == "matmul" and prim in _ELEMENTWISE:
            # unrolled MAC chain (quant.matmul_unrolled): each product is
            # a multiply-accumulate (2 flops), the explicit adds are the
            # accumulates already priced in — total 2*M*N*K, matching
            # the dot_general this chain replaces bit-for-bit
            flops = 2.0 * flops if prim == "mul" else 0.0
        rep.add(stage, op, flops, eqn_bytes(eqn), mult)


def program_cost(fn, *args, stage: str = "forward") -> CostReport:
    """Cost of ``fn(*args)``'s jaxpr; ``stage`` labels unattributed eqns."""
    closed = jax.make_jaxpr(fn)(*args)
    rep = CostReport()
    _walk(closed.jaxpr, 1.0, stage, rep)
    return rep


# -- Engine-level entry points ----------------------------------------------

def _unpack_cost(engine) -> Optional[CostReport]:
    """Cost of the per-call unpack program — None for float plans AND
    for integer-executing plans (no unpack stage exists; the eliminated
    work is the int-exec flavour's headline saving)."""
    if not engine.int_resident or engine.int_exec:
        return None
    from repro.core import quant
    return program_cost(quant.dequantize_tree, engine.params,
                        stage="unpack")


def _live_structs(engine):
    """The operand tree the model executables actually run on.

    Integer-EXECUTING plans consume the packed QTensors directly —
    tracing with them routes ``linear`` through ``quant.int_exec_einsum``
    and charges the quantise/requant epilogue where it really runs.

    Non-executing integer-resident plans feed ``live_params()`` (the
    transient float view) to the model jits — tracing with the packed
    QTensors instead would route ``linear`` through the inline-dequant
    path and charge unpack work to embed/encode twice.  ``eval_shape``
    gives the view's shapes without materialising it.
    """
    if not engine.int_resident or engine.int_exec:
        return engine.params
    from repro.core import quant
    return jax.eval_shape(quant.dequantize_tree, engine.params)


def engine_cost(engine, x=None, batch: int = 1) -> CostReport:
    """Full per-forward cost of an Engine plan (paper-table shape).

    Covers everything ``Engine.forward`` executes: the separate jitted
    unpack program of integer-resident plans (stage ``unpack``) plus the
    model program — KWT traced as its ``embed_frames``/``encode_window``
    factorisation so the stage split matches the telemetry span names;
    LM families land in one ``encode`` stage with per-op rows.
    """
    import jax.numpy as jnp

    from repro import analysis

    cfg = engine.exec_cfg
    if x is None:
        x = analysis.example_input(cfg, batch)
    rep = CostReport()
    up = _unpack_cost(engine)
    if up is not None:
        rep.merge(up)
    lp = _live_structs(engine)
    if cfg.family == "kwt":
        f, t = cfg.input_dim
        frames = jnp.zeros((x.shape[0], t, f), jnp.float32)
        window = jnp.zeros((x.shape[0], t, cfg.d_model),
                           jnp.dtype(cfg.dtype))
        rep.merge(program_cost(
            lambda p, fr: engine._mod.embed_frames(p, fr, cfg),
            lp, frames, stage="embed"))
        rep.merge(program_cost(
            lambda p, w: engine._mod.encode_window(p, w, cfg),
            lp, window, stage="encode"))
    else:
        rep.merge(program_cost(
            lambda p, xx: engine._mod.forward(p, xx, cfg),
            lp, x, stage="encode"))
    return rep


def stream_hop_cost(engine, fcfg, batch: int = 1, chunk_hops: int = 1,
                    feature_ingest: bool = False) -> CostReport:
    """Cost of one streaming hop under an Engine plan: the jitted
    ``stream.engine.stream_step`` (audio ingest: featurise + embed +
    encode) or ``stream_step_frames`` (edge-featurised ingest), plus the
    unpack program of integer-resident plans.  The detector step is not
    modelled (its per-hop work is a handful of [B] element-wise ops)."""
    import jax.numpy as jnp

    from repro.stream import engine as stream_engine

    cfg = engine.exec_cfg
    state = stream_engine.init_stream_state(cfg, fcfg, batch)
    rep = CostReport()
    up = _unpack_cost(engine)
    if up is not None:
        rep.merge(up)
    lp = _live_structs(engine)
    if feature_ingest:
        chunk = jnp.zeros((batch, chunk_hops, cfg.input_dim[0]),
                          jnp.float32)
        rep.merge(program_cost(
            lambda p, s, c: stream_engine.stream_step_frames(p, s, c, cfg),
            lp, state, chunk, stage="encode"))
    else:
        chunk = jnp.zeros((batch, chunk_hops * fcfg.hop_len), jnp.float32)
        rep.merge(program_cost(
            lambda p, s, c: stream_engine.stream_step(p, s, c, cfg, fcfg),
            lp, state, chunk, stage="encode"))
    return rep
