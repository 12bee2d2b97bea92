"""The program's own host spans in a profiler trace (``.xplane.pb``),
step by step.

``StreamLanes.hop`` opens ``cell.hop`` around one child span per host
phase of a hop (``cell.hop.copy_in``, ``cell.hop.dispatch``,
``cell.hop.wait``, ``cell.hop.copy_out``), written into the profiler's
host plane while it collects.  A run's per-phase metrics read these
spans' seconds in the window from the trace's reduction
(``Reduction.program_s``).  Here :func:`steps` gives each window step's
harness ``hop`` span, the program's spans inside it and the device
module that ran for it, to check that the phases cover the hop, and
:func:`clock_offsets_ns` reads the host-device clock offset from them.
A trace recorded before the program had the spans gives steps with no
program spans and no offsets, instead of raising.

Host spans are intervals on the host clock alone, so their durations do
not depend on how the device plane is aligned to it.
"""

from __future__ import annotations

import dataclasses

from yardstick import trace as trace_mod

PREFIX = trace_mod.PROGRAM_PREFIX
HOP_SPAN = "cell.hop"
HARNESS_HOP = "hop"        # the harness's span around ``StreamLanes.hop``
PHASES = ("copy_in", "dispatch", "wait", "copy_out")
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Step:
    hop: tuple                 # the harness's ``hop`` span (start, end) ns
    program: dict              # program span name -> [(start, end), ...]
    device: list               # device module (start, end) ns, in order

    def phase_ns(self, phase: str) -> float:
        return sum(e - s for s, e in self.program.get(
            f"{HOP_SPAN}.{phase}", ()))


def _load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _host_events(pd) -> list:
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def _window(events, path) -> tuple:
    for s, e, name in events:
        if name == trace_mod.WINDOW_SPAN:
            return s, e
    raise ValueError(f"{path}: no {trace_mod.WINDOW_SPAN!r} span")


def steps(path: str) -> list:
    """One :class:`Step` per harness ``hop`` span inside the window, in
    order.  Device modules are paired with hops by order: the k-th
    module that overlaps the window ran for the k-th hop."""
    pd = _load(path)
    events = _host_events(pd)
    w0, w1 = _window(events, path)
    hops = sorted((s, e) for s, e, name in events
                  if name == HARNESS_HOP and w0 <= s and e <= w1)
    device = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in pd.planes if plane.name.startswith("/device:")
        for line in plane.lines if line.name == MODULES_LINE
        for ev in line.events
        if ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0)
    out = []
    for k, (h0, h1) in enumerate(hops):
        program: dict = {}
        for s, e, name in events:
            if name.startswith(PREFIX) and h0 <= s and e <= h1:
                program.setdefault(name, []).append((s, e))
        out.append(Step(hop=(h0, h1), program=program,
                        device=device[k:k + 1] if len(device) == len(hops)
                        else []))
    return out


def clock_offsets_ns(step: Step) -> tuple | None:
    """(device module start − ``cell.hop.dispatch`` start, ``cell.hop.wait``
    end − device module end) of one step, in ns; ``None`` without the
    spans or the module.  A program starts after its dispatch begins and
    ends before the wait for it ends, so on aligned clocks both are
    positive; a negative first number means the device plane leads the
    host plane by at least that much."""
    dispatch = step.program.get(f"{HOP_SPAN}.dispatch")
    wait = step.program.get(f"{HOP_SPAN}.wait")
    if not dispatch or not wait or not step.device:
        return None
    d0, d1 = step.device[0]
    return d0 - dispatch[0][0], wait[-1][1] - d1
