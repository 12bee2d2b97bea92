"""Tests of the program-span reduction, on the CPU.

    python -m pytest bench/tests -q

``data/tiny_audio_256_spans.xplane.pb`` holds a few window steps of
``tiny-audio-256`` recorded on a TPU v5e with the harness's spans and
the program's ``cell.hop`` spans; ``data/tiny_audio_64lanes.xplane.pb``
was recorded before the program had them.
"""

from __future__ import annotations

import statistics
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from yardstick import spans, trace  # noqa: E402

DATA = BENCH / "tests" / "data"
SPANS = str(DATA / "tiny_audio_256_spans.xplane.pb")
OLD = str(DATA / "tiny_audio_64lanes.xplane.pb")
CHILDREN = tuple(f"{spans.HOP_SPAN}.{p}" for p in spans.PHASES)


@pytest.fixture(scope="module")
def steps():
    return spans.steps(SPANS)


def _dur(iv):
    return iv[1] - iv[0]


def test_each_window_step_has_one_cell_hop_and_its_four_phases(steps):
    assert len(steps) >= 3
    for st in steps:
        (hop,) = st.program[spans.HOP_SPAN]
        assert set(st.program) == {spans.HOP_SPAN, *CHILDREN}
        kids = [st.program[name] for name in CHILDREN]
        assert all(len(k) == 1 for k in kids)
        kids = [k[0] for k in kids]
        assert hop[0] <= kids[0][0]
        for prev, nxt in zip(kids, kids[1:]):
            assert prev[1] <= nxt[0]                 # in order, no overlap
        assert kids[-1][1] <= hop[1]


def test_phases_cover_cell_hop_and_cell_hop_covers_the_harness_hop(steps):
    for st in steps:
        (hop,) = st.program[spans.HOP_SPAN]
        covered = sum(st.phase_ns(p) for p in spans.PHASES)
        assert covered >= 0.90 * _dur(hop)
        assert _dur(hop) >= 0.95 * _dur(st.hop)


def test_phase_means_per_step(steps):
    """The per-step means a phase metric reads are the window's phase
    time (the reduction's ``program_s``) over its steps: here every
    step's span lies inside the window."""
    sec = trace.reduce_file(SPANS).program_s
    for p in spans.PHASES:
        want = statistics.fmean(1e-6 * st.phase_ns(p) for st in steps)
        got = 1e3 * sec[f"{spans.HOP_SPAN}.{p}"] / len(steps)
        assert got == pytest.approx(want, rel=1e-9)
    assert sum(sec[c] for c in CHILDREN) <= sec[spans.HOP_SPAN]


def test_a_trace_without_program_spans_reads_nothing():
    assert trace.reduce_file(OLD).program_s == {}
    assert all(st.program == {} for st in spans.steps(OLD))
    assert all(spans.clock_offsets_ns(st) is None
               for st in spans.steps(OLD))


def test_program_spans_leave_the_trace_reduction_alone():
    """The program's spans are named apart from the harness's, so the
    reduction labels idle gaps by ``prepare_chunk`` and ``hop`` alone."""
    red = trace.reduce_file(SPANS)
    assert set(red.gaps) <= {*trace.HOST_SPANS, "other"}
    assert not any(name.startswith(spans.PREFIX)
                   for name in (*trace.HOST_SPANS, trace.WINDOW_SPAN))


# -- the host-device clock ----------------------------------------------------

def _launches_and_modules(path):
    """Per window step, in order: the end of the host's launch of the
    step's program (the TPU runtime's ``tpu::System::Execute``) and the
    start of the device module that ran it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    (w0, w1) = [(s, e) for s, e, n in host if n == trace.WINDOW_SPAN][0]
    launch = sorted(e for s, e, n in host
                    if n == "tpu::System::Execute" and w0 <= s <= w1)
    modules = sorted(ev.start_ns for plane in pd.planes
                     if plane.name.startswith("/device:")
                     for line in plane.lines
                     if line.name == spans.MODULES_LINE
                     for ev in line.events if w0 <= ev.start_ns <= w1)
    assert len(launch) == len(modules)
    return launch, modules


def test_clock_offsets_per_step(steps):
    """On this trace the device module of every step starts after its
    dispatch began (the launch returns inside ``cell.hop.dispatch``) and
    ends before the wait for it ends: the device plane does not lead."""
    offsets = [spans.clock_offsets_ns(st) for st in steps]
    assert all(o is not None for o in offsets)
    start = [a * 1e-3 for a, _ in offsets]          # microseconds
    end = [b * 1e-3 for _, b in offsets]
    assert 1000 < min(start) and max(start) < 1600
    assert 400 < min(end) and max(end) < 800


def test_device_plane_alignment_differs_between_sessions():
    """The offset between the planes is set per profiler session: in the
    trace with the program's spans each module starts after the host's
    launch of it returned; in the older trace every module starts over a
    millisecond before."""
    launch, modules = _launches_and_modules(SPANS)
    after = [(m - e) * 1e-3 for e, m in zip(launch, modules)]
    assert all(100 < a < 500 for a in after)
    launch, modules = _launches_and_modules(OLD)
    before = [(e - m) * 1e-3 for e, m in zip(launch, modules)]
    assert all(1000 < b < 1400 for b in before)
