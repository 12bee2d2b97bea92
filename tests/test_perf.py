"""repro.perf + telemetry.flight: the PR-9 contracts.

* the static cost model's FLOPs/bytes agree with hand-counted analytic
  formulas for a linear layer and the full KWT block (projections +
  scores + MLP + head), exactly;
* matmul FLOPs are invariant across float/lut_float/lut/pallas for
  identical math (the backends change softmax/GELU realisation and
  weight residency, never the linear algebra);
* the ledger round-trips entries and the regression gate trips on a 2×
  latency / any-ROM-growth regression and stays quiet on healthy runs
  (including the ``python -m repro.perf regress`` exit codes);
* the flight recorder's ring wraps at capacity, each anomaly dumps
  exactly once per incident, and the post-mortem attributes slow hops
  to a named stage;
* ``latency_summary`` reports n=0 on empty reservoirs instead of
  raising (the cold-cell export path).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import perf, runtime, telemetry
from repro.configs import registry
from repro.models import kwt, layers
from repro.perf import __main__ as perf_cli
from repro.stream import features
from repro.telemetry.cell import make_cell_metrics
from repro.telemetry.flight import FlightConfig, FlightRecorder

CFG = registry.get("kwt-tiny").smoke


@pytest.fixture(scope="module")
def params():
    return kwt.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engines(params):
    return {b: runtime.compile_model(CFG, params, backend=b)
            for b in ("float", "lut_float", "lut", "pallas")}


@pytest.fixture(scope="module")
def resident(params):
    """The integer-resident lut plan that still dequantises per call:
    the one plan flavour with an unpack stage (int-exec plans have none)."""
    return runtime.compile_model(CFG, params, backend="lut",
                                 integer_exec=False)


# ---------------------------------------------------------------------------
# cost model: hand-counted ground truth
# ---------------------------------------------------------------------------

def test_linear_flops_bytes_hand_counted():
    m, k, n = 5, 7, 11
    x = jnp.zeros((m, k), jnp.float32)
    w = jnp.zeros((k, n), jnp.float32)
    rep = perf.program_cost(
        lambda a, b: layers.linear(a, b, "mk,kn->mn"), x, w)
    assert rep.flops == 2 * m * n * k                  # one dot, 2MNK
    assert rep.bytes == 4 * (m * k + k * n + m * n)    # f32 in + out
    assert rep.matmul_flops == rep.flops


@pytest.mark.parametrize("batch", [1, 4])
def test_kwt_matmul_flops_hand_counted(engines, batch):
    """Full KWT-Tiny forward vs the analytic per-layer matmul count."""
    f, t_in = CFG.input_dim
    d, h = CFG.d_model, CFG.n_heads
    dh = CFG.resolved_head_dim
    t = t_in + 1                                   # + cls token
    mlp = CFG.d_ff
    per_layer = (3 * 2 * t * d * (h * dh)          # wq/wk/wv projections
                 + 2 * 2 * h * t * t * dh          # scores + attn @ v
                 + 2 * t * (h * dh) * d            # wo
                 + 2 * t * d * mlp + 2 * t * mlp * d)   # mlp w1/w2
    expect = batch * (2 * t_in * d * f             # embed_frames linear
                      + CFG.n_layers * per_layer
                      + 2 * d * CFG.n_classes)     # cls head
    rep = perf.engine_cost(engines["float"], batch=batch)
    assert rep.matmul_flops == expect


def test_matmul_flops_invariant_across_backends(engines):
    """Identical math on every backend: the LUT/Pallas plans re-route
    softmax/GELU (and pay unpack), but dot_general work is pinned."""
    reps = {b: perf.engine_cost(e, batch=2) for b, e in engines.items()}
    counts = {b: r.matmul_flops for b, r in reps.items()}
    assert len(set(counts.values())) == 1, counts


def test_unpack_stage_only_for_int_resident(engines, resident):
    stages_f = perf.engine_cost(engines["float"]).by_stage()
    stages_q = perf.engine_cost(resident).by_stage()
    assert "unpack" not in stages_f
    assert "unpack" not in perf.engine_cost(engines["lut"]).by_stage()
    assert stages_q["unpack"].flops > 0
    # unpack work scales with params, not batch
    stages_q8 = perf.engine_cost(resident, batch=8).by_stage()
    assert stages_q8["unpack"].flops == stages_q["unpack"].flops


def test_stage_split_matches_span_names(engines):
    """Stages mirror the telemetry span vocabulary: embed/encode for the
    offline forward, + featurise for the audio-ingest streaming hop."""
    rep = perf.engine_cost(engines["float"], batch=1)
    assert set(rep.by_stage()) == {"embed", "encode"}
    fcfg = features.FrontendConfig()
    hop = perf.stream_hop_cost(engines["float"], fcfg, batch=2)
    assert "featurise" in hop.by_stage()
    hop_f = perf.stream_hop_cost(engines["float"], fcfg, batch=2,
                                 feature_ingest=True)
    assert "featurise" not in hop_f.by_stage()


def test_softmax_gelu_rows_and_report_shape(resident):
    rep = perf.engine_cost(resident, batch=1)
    ops = {op for (_, op) in rep.lines}
    assert {"softmax", "gelu", "matmul", "norm"} <= ops
    rows = rep.rows(perf.PAPER_MCU)
    assert all({"stage", "op", "flops", "bytes_moved",
                "arithmetic_intensity", "est_cycles"} <= set(r)
               for r in rows)
    assert "est_cycles" in rep.table(perf.PAPER_MCU)
    w = rep.stage_weights(perf.PAPER_MCU)
    assert abs(sum(w.values()) - 1.0) < 1e-9 and "unpack" in w


# ---------------------------------------------------------------------------
# roofline machine model
# ---------------------------------------------------------------------------

def test_machine_model_math():
    m = perf.MachineModel(name="toy", peak_flops=100.0, mem_bw=10.0,
                          clock_hz=50.0)
    assert m.ridge == 10.0
    assert m.attainable(5.0) == 50.0           # memory side
    assert m.attainable(20.0) == 100.0         # compute side
    assert m.verdict(5.0) == "memory-bound"
    assert m.verdict(20.0) == "compute-bound"
    assert m.time_s(200.0, 10.0) == 2.0        # compute term dominates
    assert m.cycles(200.0, 10.0) == 100.0


def test_roofline_terms_keys_and_verdict():
    m = perf.MachineModel(name="toy", peak_flops=100.0, mem_bw=10.0)
    row = perf.roofline_terms(50.0, 100.0, measured_s=2.0, machine=m)
    assert row["bound"] == "memory-bound"
    assert row["achieved_flops_per_s"] == 25
    assert row["achieved_pct_of_roof"] == 500.0      # roof = 0.5*10
    assert row["achieved_pct_of_peak"] == 25.0
    assert {"flops", "bytes_moved", "arithmetic_intensity"} <= set(row)


def test_calibrate_measures_positive_envelope():
    m = perf.calibrate(n=128, stream_mb=4, reps=1)
    assert m.peak_flops > 0 and m.mem_bw > 0 and m.source == "measured"
    assert m.id.startswith("measured-")


# ---------------------------------------------------------------------------
# ledger + regression gate
# ---------------------------------------------------------------------------

PROV = {"git_commit": "t", "jax_version": "-", "device": "-",
        "timestamp": "-", "calibration": None}


def _seed(path, latencies, rom=1500):
    perf.append(path, [perf.entry("kwt-tiny", "lut", 64, la,
                                  "us_per_forward", rom_bytes=rom,
                                  prov=PROV) for la in latencies])


def test_ledger_round_trip(tmp_path):
    path = str(tmp_path / "h.jsonl")
    e = perf.entry("kwt-tiny", "lut", 64, 612.5, "us_per_forward",
                   rom_bytes=1500, extra={"bound": "memory-bound"},
                   prov=PROV)
    assert perf.append(path, e) == 1
    assert perf.append(path, [e, e]) == 2
    back = perf.read(path)
    assert len(back) == 3 and back[0] == e
    assert perf.read(str(tmp_path / "missing.jsonl")) == []


def test_regress_no_trip_on_healthy(tmp_path):
    path = str(tmp_path / "h.jsonl")
    _seed(path, [600.0, 610.0, 605.0, 608.0])
    v = perf.regress(path)
    assert v.ok and v.checked == 1 and not v.failures


def test_regress_trips_on_latency(tmp_path):
    path = str(tmp_path / "h.jsonl")
    _seed(path, [600.0, 610.0, 605.0, 1300.0])     # >2x the median
    v = perf.regress(path)
    assert not v.ok and "latency" in v.failures[0]


def test_regress_trips_on_any_rom_growth(tmp_path):
    path = str(tmp_path / "h.jsonl")
    _seed(path, [600.0, 610.0])
    perf.append(path, perf.entry("kwt-tiny", "lut", 64, 600.0,
                                 "us_per_forward", rom_bytes=1501,
                                 prov=PROV))
    v = perf.regress(path)
    assert not v.ok and "rom_bytes" in v.failures[0]


def test_regress_first_entry_seeds_baseline(tmp_path):
    path = str(tmp_path / "h.jsonl")
    _seed(path, [600.0])
    v = perf.regress(path)
    assert v.ok and v.checked == 0 and v.skipped == 1


def test_regress_baseline_is_median_not_last(tmp_path):
    """One noisy prior run must not move the baseline."""
    path = str(tmp_path / "h.jsonl")
    _seed(path, [600.0, 605.0, 6000.0, 610.0])     # spike mid-history
    assert perf.regress(path).ok


def test_regress_cli_exit_codes(tmp_path):
    bad = str(tmp_path / "bad.jsonl")
    _seed(bad, [600.0, 610.0, 1300.0])
    assert perf_cli.main(["regress", "--history", bad]) == 1
    good = str(tmp_path / "good.jsonl")
    _seed(good, [600.0, 610.0, 605.0])
    assert perf_cli.main(["regress", "--history", good]) == 0
    assert perf_cli.main(["regress", "--selftest"]) == 0


def test_provenance_fields():
    p = perf.provenance(perf.PAPER_MCU)
    assert {"git_commit", "jax_version", "device", "timestamp",
            "calibration"} <= set(p)
    assert p["calibration"] == perf.PAPER_MCU.id


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

@pytest.fixture()
def flight(tmp_path):
    m = make_cell_metrics(telemetry.Registry())
    fr = FlightRecorder(m, FlightConfig(capacity=8, shed_spike=3,
                                        min_hops=4,
                                        dump_dir=str(tmp_path)),
                        stage_weights={"encode": 0.7, "featurise": 0.3})
    return m, fr


def test_flight_ring_wraps(flight):
    _, fr = flight
    for i in range(20):
        fr.record_hop(float(i))
    assert len(fr) == 8
    win = fr.window()
    assert [r.seq for r in win] == list(range(12, 20))
    assert win[-1].duration_ms == 19.0


def test_flight_shed_spike_dumps_once(flight):
    m, fr = flight
    for _ in range(4):
        fr.record_hop(1.0)
    m.rejected.inc(3)
    path = fr.record_hop(1.0)
    assert path is not None
    art = json.load(open(path))
    assert art["reason"] == "shed_spike"
    assert art["admission"]["rejected_in_window"] == 3
    # still tripped: no second dump until the window clears
    assert fr.record_hop(1.0) is None
    # spike rolls out of the 8-hop window -> re-arms -> a NEW spike dumps
    for _ in range(8):
        assert fr.record_hop(1.0) is None
    m.rejected.inc(3)
    assert fr.record_hop(1.0) is not None
    assert len(fr.dumps) == 2


def test_flight_slo_burn_uses_budget_gauge(flight):
    m, fr = flight
    m.latency_budget.set(10.0)
    for _ in range(3):
        assert fr.record_hop(50.0) is None     # below min_hops: no dump
    path = fr.record_hop(50.0)
    assert path is not None and "slo_burn" in path
    att = json.load(open(path))["attribution"]
    assert att["slowest_stage"] == "encode"    # 0.7 weight wins
    assert att["method"] == "cost-model-weights"
    assert att["stage_ms"]["encode"] == pytest.approx(35.0)


def test_flight_swap_failure_via_check(flight):
    m, fr = flight
    for _ in range(2):
        fr.record_hop(1.0)
    assert fr.check() is None
    m.swap_failures.inc()                      # probe-parity refusal
    path = fr.check()                          # between hops, no new slot
    assert path is not None
    assert json.load(open(path))["reason"] == "swap_failure"
    assert len(fr) == 2                        # check() consumed no slot


def test_flight_attribution_prefers_measured_spans(flight):
    m, fr = flight
    m.latency_budget.set(10.0)
    for _ in range(4):
        fr.record_hop(50.0, spans={"featurise": 40.0, "encode": 9.0})
    att = fr.attribution()
    assert att["method"] == "measured-spans"
    assert att["slowest_stage"] == "featurise"


def test_flight_lazy_stage_weights_resolve_once(tmp_path):
    m = make_cell_metrics(telemetry.Registry())
    calls = []

    def weights():
        calls.append(1)
        return {"encode": 1.0}

    fr = FlightRecorder(m, FlightConfig(capacity=4,
                                        dump_dir=str(tmp_path)),
                        stage_weights=weights)
    fr.record_hop(1.0)
    fr.dump("manual")
    fr.dump("manual")
    assert len(calls) == 1                     # resolved once, then cached


def test_flight_dump_artifact_schema(flight):
    m, fr = flight
    for i in range(6):
        fr.record_hop(1.0 + i)
    path = fr.dump("manual")
    art = json.load(open(path))
    assert {"reason", "provenance", "attribution", "admission",
            "hotswap", "trace", "hop_latency"} <= set(art)
    assert len(art["trace"]) == 6
    assert art["provenance"]["git_commit"]
    assert os.path.exists(path)


# ---------------------------------------------------------------------------
# integration: Engine.describe(cost=True), empty latency_summary
# ---------------------------------------------------------------------------

def test_describe_cost_appends_table(resident):
    out = resident.describe(cost=True)
    assert "cost/fwd" in out and "est_cycles" in out
    assert "| unpack |" in out                 # the paper-style table


def test_latency_summary_empty_reports_n0():
    s = telemetry.latency_summary([], unit="ms")
    assert s == {"n": 0, "mean_ms": 0.0, "p50_ms": 0.0, "p95_ms": 0.0,
                 "p99_ms": 0.0}
    # cold histogram (no observations yet) exports without raising
    h = telemetry.Registry().histogram("cold_ms", unit="ms")
    assert h.summary()["n"] == 0
    assert np.isfinite(list(s.values())[1])


def test_latency_summary_count_override_empty():
    s = telemetry.latency_summary([], unit="us", count=7)
    assert s["n"] == 7 and s["p99_us"] == 0.0
