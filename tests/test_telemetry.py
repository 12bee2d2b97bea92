"""repro.telemetry: tracing, metrics, quantisation-health taps.

The PR-7 contracts:

* taps-on logits are bit-identical to the untapped plan on every backend
  (the aux comes from a separate jitted program; the serving executable
  never changes);
* histogram quantiles are correct, including after the ring reservoir
  wraps;
* emitted traces validate against the Chrome trace-event schema and the
  Prometheus text exposition validates as Prometheus;
* the telemetry-disabled fast path adds no per-call allocation in
  ``Engine.forward`` (one shared no-op span, one global read).
"""

import glob
import json
import os
import tracemalloc

import jax
import numpy as np
import pytest

from repro import runtime
from repro import telemetry
from repro.configs import registry
from repro.models import kwt
from repro.telemetry import check as tcheck
from repro.telemetry import taps
from repro.telemetry import trace as trace_mod

KEY = jax.random.PRNGKey(0)
CFG = registry.get("kwt-tiny").config


@pytest.fixture(scope="module")
def params():
    return kwt.init_params(CFG, KEY)


@pytest.fixture(scope="module")
def mfcc():
    return 0.5 * jax.random.normal(jax.random.PRNGKey(1),
                                   (2, *CFG.input_dim))


@pytest.fixture(autouse=True)
def _tracing_off():
    yield
    telemetry.disable()


# ---------------------------------------------------------------------------
# taps: bit-identity + health stats
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["float", "lut", "pallas"])
def test_taps_logits_bit_identical(params, mfcc, backend):
    eng = runtime.compile_model(CFG, params, backend=backend)
    engt = runtime.compile_model(CFG, params, backend=backend, taps=True)
    base = np.asarray(eng.forward(mfcc))
    logits, aux = engt.forward(mfcc)
    assert np.array_equal(np.asarray(logits), base)
    # per-layer aux is present, scoped, and finite
    assert "block0/softmax" in aux
    assert "lut_oob_frac" in aux["block0/softmax"]
    assert "embed" in aux and "logits" in aux
    for site, stats in aux.items():
        for stat, v in stats.items():
            assert np.isfinite(float(v)), f"{site}/{stat} not finite"


def test_taps_off_by_default_and_no_aux(params, mfcc):
    eng = runtime.compile_model(CFG, params, backend="lut")
    assert eng.taps is False
    out = eng.forward(mfcc)
    assert not isinstance(out, tuple)


def test_taps_report_saturation_when_activations_hot(params):
    """Scores far beyond the eq-9 grid edge must read as saturated.
    Uses the non-executing resident plan: the int-exec flavour's input
    quantiser clips hot activations INSIDE the linears, so its embed
    output is already bounded — the tap's pre-clip view needs the float
    activation path."""
    hot = 300.0 * jax.random.normal(jax.random.PRNGKey(2),
                                    (2, *CFG.input_dim))
    engt = runtime.compile_model(CFG, params, backend="lut", taps=True,
                                 integer_exec=False)
    _, aux = engt.forward(hot)
    assert float(aux["embed"]["int8_sat_frac"]) > 0.5
    assert float(aux["embed"]["q24_headroom_bits"]) < 0


def test_tap_calls_are_noops_without_collector():
    """Model code calls taps unconditionally; inactive they must emit
    nothing and leave no trace in the jaxpr."""
    assert not taps.active()
    taps.tap_gelu(np.zeros((4,)))
    taps.tap_softmax(np.zeros((2, 4)))
    with taps.collecting() as col:
        assert taps.active()
        taps.tap_gelu(np.zeros((4,)))
    assert not taps.active()
    assert len(col) == 1 and col[0][0] == "gelu"


# ---------------------------------------------------------------------------
# histogram quantiles
# ---------------------------------------------------------------------------

def test_histogram_quantiles_match_numpy():
    h = telemetry.Histogram("lat", unit="ms", capacity=2048)
    vals = np.random.RandomState(0).lognormal(0, 1, 1000)
    for v in vals:
        h.observe(v)
    for q in (0.5, 0.95, 0.99):
        assert h.quantile(q) == pytest.approx(
            np.percentile(vals, 100 * q), rel=1e-12)
    s = h.summary()
    assert s["n"] == 1000
    assert s["p50_ms"] == pytest.approx(np.percentile(vals, 50), abs=1e-3)


def test_histogram_ring_keeps_latest_window():
    h = telemetry.Histogram("lat", capacity=10)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100                      # true count survives the ring
    assert sorted(h.values()) == [float(v) for v in range(90, 100)]
    assert h.quantile(0.5) == pytest.approx(np.percentile(range(90, 100), 50))


def test_latency_summary_is_the_shared_schema():
    s = telemetry.latency_summary([1.0, 2.0, 3.0], unit="us")
    assert set(s) == {"n", "mean_us", "p50_us", "p95_us", "p99_us"}
    assert s["n"] == 3 and s["p50_us"] == 2.0


# ---------------------------------------------------------------------------
# trace + Prometheus format validation
# ---------------------------------------------------------------------------

def test_trace_validates_against_chrome_schema(tmp_path):
    with telemetry.tracing() as tr:
        with telemetry.span("forward", {"backend": "float"}):
            with telemetry.span("unpack"):
                pass
            with telemetry.span("encode"):
                pass
        tr.instant("marker")
    path = tr.save(str(tmp_path / "trace.json"))
    n = telemetry.validate_chrome_trace(path)
    assert n == 4
    obj = json.load(open(path))
    by_name = {e["name"]: e for e in obj["traceEvents"]}
    assert by_name["unpack"]["args"]["parent"] == "forward"
    assert by_name["encode"]["ph"] == "X" and by_name["encode"]["dur"] >= 0
    assert by_name["marker"]["ph"] == "i"


def test_trace_schema_violations_rejected():
    with pytest.raises(tcheck.TelemetryFormatError):
        telemetry.validate_chrome_trace({"events": []})    # wrong key
    with pytest.raises(tcheck.TelemetryFormatError):
        telemetry.validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "ts": 0,
                              "pid": 1, "tid": 1}]})       # X without dur


def test_span_coverage_accounts_children():
    with telemetry.tracing() as tr:
        with tr.span("forward"):
            with tr.span("unpack"):
                sum(range(2000))
            with tr.span("encode"):
                sum(range(20000))
    cov = telemetry.span_coverage(tr, "forward")
    assert 0.5 < cov <= 1.0


def test_prometheus_export_validates():
    reg = telemetry.Registry()
    reg.counter("events_total", "events", {"backend": "lut"}).inc(3)
    reg.gauge("queue_depth", "depth").set(7)
    h = reg.histogram("hop_latency_ms", "latency", unit="ms")
    for v in range(50):
        h.observe(float(v))
    text = reg.to_prometheus()
    assert telemetry.validate_prometheus(text) == 7   # 1 + 1 + (3q + sum + n)
    assert 'events_total{backend="lut"} 3' in text
    with pytest.raises(tcheck.TelemetryFormatError):
        telemetry.validate_prometheus("no_type_line 1")


def test_registry_save_layout_matches_checker(tmp_path):
    reg = telemetry.Registry()
    reg.counter("c_total").inc()
    with telemetry.tracing() as tr:
        with telemetry.span("hop"):
            pass
    trace = tr.save(str(tmp_path / "t.json"))
    reg.save(str(tmp_path / "t"))
    out = tcheck.check_artifacts(trace, require_metrics=True)
    assert out["events"] == 1 and out["prom_samples"] == 1


def test_structured_log_line_is_parseable(capsys):
    line = telemetry.log("serve_done", streams=4, rtf=0.123456,
                         note="two words")
    assert line.startswith("event=serve_done ts=")
    assert "rtf=0.1235" in line and 'note="two words"' in line
    assert capsys.readouterr().out.strip() == line


# ---------------------------------------------------------------------------
# disabled fast path: no per-call allocation
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_singleton():
    telemetry.disable()
    s = telemetry.span("anything")
    assert s is telemetry.NOOP_SPAN
    assert s is telemetry.span("something_else", {"k": 1})


def test_disabled_span_allocates_nothing():
    telemetry.disable()
    for _ in range(4):                      # warm any lazy caches
        with telemetry.span("warm"):
            pass
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot()
    for _ in range(200):
        with telemetry.span("hot"):
            pass
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grew = [st for st in snap2.compare_to(snap1, "lineno")
            if st.size_diff > 0 and "repro/telemetry" in str(st.traceback)]
    assert not grew, f"disabled span path allocated: {grew}"


def test_span_is_noop_with_no_tracer_and_no_profiler():
    telemetry.disable()
    assert not trace_mod.profiling()
    assert telemetry.span("cell.hop", {"seq": 0}) is telemetry.NOOP_SPAN


def _host_events(trace_dir):
    """(start, end, name, stats) of every host event in the one
    ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    return sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                   dict(ev.stats))
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events)


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_span_annotates_the_profiler_trace_without_a_tracer(tmp_path):
    """While the JAX profiler collects, ``span`` opens a TraceAnnotation
    (args as metadata) even with no tracer installed; before and after,
    it is the shared no-op."""
    telemetry.disable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace_mod.profiling()
        outer = telemetry.span("outer", {"seq": 7})
        assert outer is not telemetry.NOOP_SPAN
        with outer:
            with telemetry.span("inner"):
                sum(range(1000))
    finally:
        jax.profiler.stop_trace()
    assert telemetry.span("outer") is telemetry.NOOP_SPAN
    evs = {e[2]: e for e in _host_events(tmp_path)
           if e[2] in ("outer", "inner")}
    assert evs["outer"][3] == {"seq": 7}
    assert evs["outer"][0] <= evs["inner"][0] <= evs["inner"][1] \
        <= evs["outer"][1]


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_tracer_span_also_annotates_the_profiler_trace(tmp_path):
    """One span system: under a Tracer and the profiler at once, a span is
    both a Chrome event and a profiler annotation of the same name."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.tracing() as tr:
            with telemetry.span("forward", {"backend": "float"}):
                with tr.span("encode"):
                    pass
    finally:
        jax.profiler.stop_trace()
    assert [e["name"] for e in tr.events] == ["encode", "forward"]
    names = [e[2] for e in _host_events(tmp_path)]
    assert names.count("forward") == 1 and names.count("encode") == 1


def test_tracer_takes_no_profiler_flag():
    """The profiler's own state decides annotation; there is no flag."""
    with pytest.raises(TypeError):
        telemetry.Tracer(profiler=True)
    with pytest.raises(TypeError):
        telemetry.enable(profiler=True)
    with pytest.raises(TypeError):
        with telemetry.tracing(profiler=True):
            pass


def test_engine_forward_disabled_path_unchanged(params, mfcc):
    """With tracing off and taps unplanned, forward must be the plain
    one-jit call — same executable, same result object type."""
    telemetry.disable()
    eng = runtime.compile_model(CFG, params, backend="float")
    base = np.asarray(eng.forward(mfcc))
    with telemetry.tracing() as tr:
        traced = np.asarray(eng.forward(mfcc))
    assert np.array_equal(base, traced)     # tracing never changes numerics
    names = {e["name"] for e in tr.events}
    # float params = no unpack program = no unpack span (the stage does
    # not exist for this plan, so nothing is attributed to it)
    assert names == {"forward", "encode"}
    after = np.asarray(eng.forward(mfcc))   # disabled again -> no new events
    assert np.array_equal(base, after)
    assert len(tr.events) == 2
