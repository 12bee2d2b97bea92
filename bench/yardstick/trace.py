"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* The window is the harness's ``bench_window`` span on the host.
* Busy time is the union of the intervals in which an operation ran on a
  device (the device planes' ``XLA Ops`` line), clipped to the window and
  averaged over the devices that ran any.
* Scope time is the device time of the operations whose op name path
  (the trace's ``tf_op``, read by :mod:`yardstick.xplane`) carries a stage
  of the stream engine (``featurise``, ``embed``, ``encode``: the
  program's ``jax.named_scope`` names); the rest, such as the copies of
  lane state and ops that carry no name, counts as ``other``.  Each op
  counts under one stage, the first of ``SCOPES`` on its path, and the
  breakdown names it with that stage (``encode/fusion``).
* Named time (``named_s``) is the device time of the operations under
  each name found anywhere on their name path, counted the same way but
  for every name: each ``jax.named_scope`` (and JAX's own, such as
  ``while`` and ``body`` of a loop, or an einsum's subscripts), nested
  ones each in full.  The transforms (``jit(...)``) and the op itself,
  the path's last part, are not names.  A new scope in the program can
  be read here with no change to this module.
* Program time (``program_s``) is the host seconds of each of the
  program's own spans (``cell.`` and below, written by
  ``repro.telemetry.span``), clipped to the window and summed by name.
* Each idle gap of the device is labelled by the harness span
  (``prepare_chunk``, ``hop``, ``lm_step``, ``submit``) the host was in
  at its middle.
* Program runs are the device planes' ``XLA Modules`` events, named
  ``<jitted function>(<fingerprint>)``: one name for each compiled
  program.  A program's device time is the union of the intervals of the
  ops that start in its runs (a loop's op spans the ops of its body, so
  a plain sum would count them twice), and each name gets its runs that
  start in the window counted, so that a reader can tell a program that
  runs every step from one that runs on some.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

import numpy as np

SCOPES = ("featurise", "embed", "encode")
HOST_SPANS = ("prepare_chunk", "hop", "lm_step", "submit")
WINDOW_SPAN = "bench_window"
PROGRAM_PREFIX = "cell."      # the program's span namespace
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    scope_s: dict
    ops: dict                 # op name -> device seconds
    gaps: dict                # host span -> idle device seconds
    devices: int
    module_s: dict = dataclasses.field(default_factory=dict)
    #                           program name -> device seconds of its ops
    module_runs: dict = dataclasses.field(default_factory=dict)
    #                           program name -> runs started in the window
    named_s: dict = dataclasses.field(default_factory=dict)
    #                           name on an op path -> device seconds
    program_s: dict = dataclasses.field(default_factory=dict)
    #                           program span name -> host seconds

    def top_ops(self, n: int) -> list:
        return [[k, float(v)] for k, v in
                sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int) -> list:
        return [[k, float(v)] for k, v in
                sorted(self.gaps.items(), key=lambda kv: -kv[1])[:n]]


def scope_of(op_path: str) -> str:
    """The stream-engine stage an op's name path names, or ``other``."""
    parts = op_path.split("/")
    for s in SCOPES:
        if s in parts:
            return s
    return "other"


def names_on(op_path: str) -> set:
    """The names on an op's name path: every part but the last (the op)
    that is no transform such as ``jit(joint)``."""
    return {p for p in op_path.split("/")[:-1]
            if p and not (p.endswith(")") and "(" in p)}


def _clipped_seconds(spans, w0: float, w1: float) -> dict:
    """Seconds of ``(start_ns, end_ns, name)`` spans inside [w0, w1),
    summed by name."""
    out: dict = {}
    for s, e, name in spans:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def short_name(hlo_text: str) -> str:
    """``fusion.12`` of ``%fusion.12 = f32[...] fusion(...)``, with the
    instance number dropped so repeated ops sum: ``fusion``."""
    name = hlo_text.split(" = ", 1)[0].lstrip("%")
    base, _, num = name.rpartition(".")
    return base if base and num.isdigit() else name


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merged [start, end) intervals, sorted."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData

    from yardstick import xplane
    pd = ProfileData.from_file(path)
    op_path = xplane.op_names(path)
    window, spans, program = None, [], []
    device_ops, device_runs = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        program.append((ev.start_ns, ev.start_ns
                                        + ev.duration_ns, ev.name))
        elif plane.name.startswith("/device:"):
            evs, runs = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        evs.append((ev.start_ns, ev.start_ns
                                    + ev.duration_ns, ev.name,
                                    op_path.get(ev.name, "")))
                elif line.name == MODULES_LINE:
                    runs.extend((ev.start_ns, ev.name) for ev in line.events)
            if evs:
                device_ops.append(evs)
                device_runs.append(sorted(runs))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = window
    spans.sort()
    span_starts = np.asarray([s[0] for s in spans], np.float64)
    busy, scope_s = 0.0, collections.Counter()
    named_s = collections.Counter()
    ops, gaps = collections.Counter(), collections.Counter()
    module_s, module_runs = collections.Counter(), collections.Counter()
    for evs, runs in zip(device_ops, device_runs):
        run_starts = np.asarray([r[0] for r in runs], np.float64)
        module_runs.update(name for start, name in runs if w0 <= start < w1)
        iv, run_iv = [], collections.defaultdict(list)
        for s, e, name, path in evs:
            r = int(np.searchsorted(run_starts, s, side="right")) - 1
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            iv.append((s, e))
            sec = (e - s) * 1e-9
            scope = scope_of(path)
            scope_s[scope] += sec
            for named in names_on(path):
                named_s[named] += sec
            ops[f"{scope}/{short_name(name)}"] += sec
            if r >= 0:
                run_iv[r].append((s, e))
        for r, run in run_iv.items():
            u = _union(np.asarray(run, np.float64))
            module_s[runs[r][1]] += float(np.sum(u[:, 1] - u[:, 0])) * 1e-9
        merged = _union(np.asarray(iv, np.float64))
        busy += float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9
        edges = np.concatenate([[w0], merged.reshape(-1), [w1]])
        for g0, g1 in edges.reshape(-1, 2):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            i = int(np.searchsorted(span_starts, mid, side="right")) - 1
            label = spans[i][2] if i >= 0 and spans[i][1] >= mid else "other"
            gaps[label] += (g1 - g0) * 1e-9
    n = max(len(device_ops), 1)
    return Reduction(window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
                     scope_s={k: v / n for k, v in scope_s.items()},
                     ops={k: v / n for k, v in ops.items()},
                     gaps={k: v / n for k, v in gaps.items()},
                     devices=len(device_ops),
                     module_s={k: v / n for k, v in module_s.items()},
                     module_runs={k: v / n for k, v in module_runs.items()},
                     named_s={k: v / n for k, v in named_s.items()},
                     program_s=_clipped_seconds(program, w0, w1))


def reduce_dir(trace_dir: str) -> Reduction:
    """Reduce the one ``.xplane.pb`` the profiler wrote under
    ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"{trace_dir}: expected one .xplane.pb, "
                         f"found {found}")
    return reduce_file(found[0])
