"""Tests of what the per-layer readers receive, on the CPU.

    python -m pytest bench/tests -q

The recorded chip traces in ``data/`` are reduced and handed to every
reader of their cell, and each reader has to read the value it read
before architecture modules, named scopes, program spans and counters
reached the readers (``data/pinned_readings.json``, recorded on XLA:CPU
by ``bench/yardstick`` at commit d319bdf), as does the breakdown.  Then
the new parts of the reduction on the same traces: ``named_s`` and
``program_s``, and the per-phase readers of the program's ``cell.hop``
spans.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from yardstick import harness, lm, lm_traffic, spans, spec, trace  # noqa: E402

DATA = BENCH / "tests" / "data"
PINNED = json.loads((DATA / "pinned_readings.json").read_text())
KIND = "TPU v5 lite"
SMOKE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab_size=256)
PHASE_READERS = tuple(f"hop_{p}_ms" for p in spans.PHASES)


def _trace(fixture: str) -> str:
    return str(DATA / f"{fixture}.xplane.pb")


def _stream_context(fixture: str):
    pin = PINNED["stream"][fixture]
    red = trace.reduce_file(_trace(fixture))
    cell = spec.load_cell(pin["workload"])
    ctx = harness._reader_context(cell, cell.config, pin["lanes"], 1,
                                  harness.Window(steps=pin["steps"]), red,
                                  KIND, counters={})
    return cell, red, ctx


def _lm_context():
    """The recorded smoke LM trace (4 slots, 18 steps, two join steps)
    with a log of the same shape: four requests under way, one ending
    before each join step and a new one joining there."""
    red = trace.reduce_file(_trace("lm_smoke"))
    cell = spec.load_cell("internlm2-chat")
    conf = dict(cell.config, model=dict(cell.config["model"], **SMOKE))
    cell = dataclasses.replace(cell, config=conf)
    mix = dict(cell.traffic, slots=4, max_len=96, requests=256, block=16,
               prompt=dict(median=24, sigma=0.7, min=8, max=64),
               output=dict(median=8, sigma=0.7, min=2, max=32))
    bank = lm_traffic.RequestBank(mix, 5, 256)
    ends = {0: 13, 1: 20}
    steps = {("warm", 0): [0, 1]}
    steps.update({r: list(range(2, ends.get(r, 27) + 1)) for r in range(4)})
    steps[4], steps[5] = list(range(14, 28)), list(range(21, 28))
    rec = lm.Log(steps=steps, window=range(10, 28))
    ctx = lm.reader_context(cell, rec, bank, red, KIND, counters={},
                            log=lambda s: None)
    return cell, red, ctx


def _context(fixture: str):
    return _lm_context() if fixture == "lm_smoke" else \
        _stream_context(fixture)


def _group(fixture: str) -> dict:
    return PINNED["lm" if fixture == "lm_smoke" else "stream"][fixture]


FIXTURES = ("tiny_audio_64lanes", "tiny_audio_256_spans", "lm_smoke")


# -- what was read before is read the same ------------------------------------

@pytest.mark.parametrize("fixture", FIXTURES)
def test_every_existing_reader_reads_its_pinned_value(fixture):
    cell, _, ctx = _context(fixture)
    pinned = _group(fixture)["readers"]
    for name, want in pinned.items():
        got = spec.metric_reader(name)(ctx)
        assert got == pytest.approx(want, rel=1e-12, abs=0), name
    assert set(pinned) <= {m["name"] for m in cell.per_layer}


@pytest.mark.parametrize("fixture", FIXTURES)
def test_the_breakdown_and_the_reduction_read_as_pinned(fixture):
    red = trace.reduce_file(_trace(fixture))
    pin = _group(fixture)
    assert red.window_s == pin["window_s"] and red.busy_s == pin["busy_s"]
    assert red.scope_s == pin["scope_s"]
    assert red.top_ops(10) == pin["top_ops"]
    assert red.idle_by_span(10) == pin["idle_gaps"]
    assert red.module_runs == pin["module_runs"]
    assert red.module_s == pin["module_s"]
    if fixture == "lm_smoke":
        assert _lm_context()[2]["phases"] == pytest.approx(pin["phases"],
                                                           rel=1e-12)


# -- named scopes -------------------------------------------------------------

def test_names_on_an_op_path():
    assert trace.names_on("jit(joint)/encode/jit(lut_softmax_2d)/"
                          "pallas_call:") == {"encode"}
    assert trace.names_on("jit(<lambda>)/while/body/closed_call/mla/"
                          "bsd,df->bsf/dot_general:") == {
        "while", "body", "closed_call", "mla", "bsd,df->bsf"}
    assert trace.names_on("transpose(jvp(moe))/experts/mul:") == {"experts"}
    assert trace.names_on("dot_general:") == set()
    assert trace.names_on("") == set()


@pytest.mark.parametrize("fixture", FIXTURES)
def test_named_time_counts_each_name_like_scope_time(fixture):
    """On the stream traces no op carries two stages, so each stage's
    named time is its scope time; every name is on some op of the trace,
    no transform is a name, and no name holds more than the ops it is
    on."""
    red = trace.reduce_file(_trace(fixture))
    ops_s = sum(red.ops.values())
    for stage in trace.SCOPES:
        if stage in red.scope_s:
            assert red.named_s[stage] == pytest.approx(red.scope_s[stage],
                                                       rel=1e-12)
    assert red.named_s
    for name, sec in red.named_s.items():
        assert "(" not in name and 0 < sec <= ops_s * (1 + 1e-12)
    if fixture == "lm_smoke":
        # the LM programs carry no scope of their own: the loop over the
        # layers and the einsums' subscripts are what is named
        assert {"while", "body", "bsd,df->bsf"} <= set(red.named_s)
        assert not set(red.named_s) & set(trace.SCOPES)


# -- program spans ------------------------------------------------------------

def test_program_time_is_the_programs_spans_in_the_window():
    red = trace.reduce_file(_trace("tiny_audio_256_spans"))
    assert set(red.program_s) == {spans.HOP_SPAN, *(
        f"{spans.HOP_SPAN}.{p}" for p in spans.PHASES)}
    phases = sum(v for k, v in red.program_s.items() if k != spans.HOP_SPAN)
    assert 0.9 * red.program_s[spans.HOP_SPAN] <= phases \
        <= red.program_s[spans.HOP_SPAN] <= red.window_s
    for fixture in ("tiny_audio_64lanes", "lm_smoke"):
        assert trace.reduce_file(_trace(fixture)).program_s == {}


def test_hop_phase_readers_read_the_program_spans():
    """Each phase reader is the mean of its span over the window's steps,
    as :func:`spans.steps` finds them one by one; together they take no
    more than a step; a trace recorded before the program had the spans
    gives them nothing to read."""
    cell, red, ctx = _stream_context("tiny_audio_256_spans")
    steps = spans.steps(_trace("tiny_audio_256_spans"))
    assert len(steps) == ctx["steps"]
    got = {p: spec.metric_reader(f"hop_{p}_ms")(ctx) for p in spans.PHASES}
    assert got == pytest.approx({p: statistics.fmean(
        1e-6 * st.phase_ns(p) for st in steps) for p in spans.PHASES},
        rel=1e-9)
    assert sum(got.values()) <= 1e3 * red.window_s / ctx["steps"]
    assert set(PHASE_READERS) <= {m["name"] for m in cell.per_layer}
    _, _, old = _stream_context("tiny_audio_64lanes")
    assert all(spec.metric_reader(name)(old) is None
               for name in PHASE_READERS)


def test_hop_phase_metrics_belong_to_every_stream_cell_alone():
    bench = spec.load_benchmark()
    stream = {w["name"] for w in bench["workloads"]
              if spec.load_cell(w["name"]).config.get("path", "stream")
              == "stream"}
    for m in bench["per_layer"]:
        if m["name"] in PHASE_READERS:
            assert set(m["workloads"]) == stream
            assert (m["layer"], m["moves"], m["source"], m["unit"]) == (
                "host path", "hop_p95_ms", "program_span", "ms")
    assert {m["name"] for m in bench["per_layer"]} >= set(PHASE_READERS)
