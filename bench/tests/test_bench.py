"""Tests of the benchmark harness, on the CPU.

    python -m pytest bench/tests -q

They load every file by name, count work from shapes, check the plain
MFCC, the reference's lookup tables and the trace reduction, and drive
whole runs with the chip check off: a sound run is correct, the controls
and a broken timed path are not, and the entry point refuses to run
without a TPU.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from yardstick import (harness, peaks, reference, spans, spec,  # noqa: E402
                       trace, work)

TRACE = BENCH / "tests" / "data" / "tiny_audio_64lanes.xplane.pb"
SEED = 2 ** 33 + 7          # wider than 32 bits, as a run's seed may be
TEST_LANES = 8


def _model(config: str) -> dict:
    with open(BENCH / "configs" / f"{config}.json") as f:
        return json.load(f)["model"]


# -- files found by name -------------------------------------------------------

def test_every_cell_config_traffic_and_metric_loads_by_name():
    bench = spec.load_benchmark()
    assert {w["name"] for w in bench["workloads"]} == {
        "kwt1-audio", "tiny-audio", "tiny-feature", "tiny-audio-256",
        "kwt1-float-audio", "internlm2-chat"}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["model"]
        if cell.config.get("path", "stream") == "lm":
            assert cell.traffic["prompt"] and cell.traffic["output"]
            assert {m["name"] for m in cell.end_to_end} == {
                "setup_s", "tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}
        else:
            assert cell.config["frontend"]
            assert cell.traffic["ingest"] in ("audio", "feature")
            assert {m["name"] for m in cell.end_to_end} == {
                "setup_s", "stream_capacity", "hop_p95_ms"}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
    featurise = {c for m in bench["per_layer"]
                 if m["name"] == "featurise_ms_per_step"
                 for c in m["workloads"]}
    assert featurise == {w["name"] for w in bench["workloads"]
                         if spec.load_cell(w["name"]).traffic.get("ingest")
                         == "audio"}


def test_a_traffic_file_added_alone_is_found(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = spec.load_benchmark()
    mix = dict(spec.load_traffic("tiny-audio"), lanes=3)
    with open(tmp_path / "bench" / "traffic" / "dummy-mix.json", "w") as f:
        json.dump(mix, f)
    bench["workloads"].append({"name": "tiny-dummy",
                               "config": "kwt-tiny-pallas",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "a cell added by files alone"})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    cell = spec.load_cell("tiny-dummy", root=tmp_path)
    assert cell.traffic["lanes"] == 3
    assert cell.bench == tmp_path / "bench"
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root=tmp_path)


# -- yardstick pieces ------------------------------------------------------------

def test_flop_counts_equal_the_hand_counts():
    kwt1 = _model("kwt1-pallas")
    s, d, ff = 99, 64, 256
    layer = {"qkv": 3 * 2 * s * d * 64, "scores": 2 * s * s * 64,
             "av": 2 * s * s * 64, "out": 2 * s * 64 * d,
             "mlp": 2 * 2 * s * d * ff}
    assert layer == {"qkv": 2_433_024, "scores": 1_254_528,
                     "av": 1_254_528, "out": 811_008, "mlp": 6_488_064}
    assert work.encoder_flops(kwt1) == 12 * sum(layer.values())
    assert 146e6 < work.step_flops(kwt1) < 148e6          # ~147 MFLOP
    tiny = _model("kwt-tiny-pallas")
    assert work.encoder_flops(tiny) == 75_168
    assert work.step_flops(tiny) == 75_168 + 2 * 16 * 12 + 2 * 12 * 2
    assert 75e3 < work.step_flops(tiny) < 77e3            # ~76 kFLOP


def test_reference_mfcc_agrees_with_the_program_frontend():
    import jax
    from repro.stream import features
    with open(BENCH / "configs" / "kwt1-pallas.json") as f:
        fr = json.load(f)["frontend"]
    rng = np.random.default_rng(0)
    audio = (0.1 * rng.standard_normal((3, 20 * fr["hop_len"]))
             ).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(features.mfcc(audio, features.FrontendConfig(**fr)))
    ctx = fr["frame_len"] - fr["hop_len"]
    padded = np.concatenate([np.zeros((3, ctx), np.float32), audio], -1)
    ref = reference.mfcc(padded, fr)                       # [3, T, F]
    assert ref.shape == (3, 20, 40)
    np.testing.assert_allclose(np.swapaxes(prog, 1, 2), ref, atol=2e-3,
                               rtol=1e-4)


def test_peaks_table_has_the_v5e_and_refuses_an_unknown_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_trace_reduction_on_a_recorded_chip_trace():
    """A trace of 8 hops of KWT-Tiny at 64 lanes, audio ingest, recorded
    on a TPU v5e with the harness's spans."""
    from jax.profiler import ProfileData
    red = trace.reduce_file(str(TRACE))
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.033205161, abs=1e-9)
    assert red.busy_s == pytest.approx(0.000652613, abs=1e-9)
    assert red.scope_s["encode"] == pytest.approx(0.000493647, abs=1e-9)
    assert red.scope_s["featurise"] == pytest.approx(0.000055917, abs=1e-9)
    # no two ops overlap here, so the stages add up to the busy time
    assert sum(red.scope_s.values()) == pytest.approx(red.busy_s, abs=1e-9)
    # idle gaps fill the rest of the window; the host was in ``hop``
    assert sum(red.gaps.values()) == pytest.approx(
        red.window_s - red.busy_s, abs=1e-9)
    assert red.idle_by_span(1)[0][0] == "hop"
    # the ops lie inside the eight program runs the trace holds
    pd = ProfileData.from_file(str(TRACE))
    modules = [e.duration_ns for p in pd.planes
               if p.name.startswith("/device:") for line in p.lines
               if line.name == "XLA Modules" for e in line.events]
    assert len(modules) == 8
    assert 0.9 * sum(modules) * 1e-9 < red.busy_s <= sum(modules) * 1e-9
    top = red.top_ops(3)
    assert top[0][0] == "encode/lut_softmax_2d"
    assert all(isinstance(v, float) for _, v in top)


def test_scope_of_reads_the_named_scope_path():
    assert trace.scope_of("jit(joint)/encode/jit(lut_softmax_2d)/"
                          "pallas_call:") == "encode"
    assert trace.scope_of("jit(joint)/featurise/jit(fft):") == "featurise"
    assert trace.scope_of("jit(joint)/jit(_take)/gather:") == "other"
    assert trace.scope_of("") == "other"
    assert trace.short_name("%fusion.12 = f32[2] fusion(...)") == "fusion"


# -- the reference -------------------------------------------------------------

def _stated(config: str) -> dict:
    with open(BENCH / "configs" / f"{config}.json") as f:
        return json.load(f)["numerics"]


@pytest.mark.parametrize("keys", [27, 99])
def test_reference_luts_follow_the_program_bit_for_bit(keys):
    """The reference rebuilds the paper's tables from the stated numerics;
    on the same float32 inputs its softmax and GELU equal the program's."""
    import jax.numpy as jnp
    from repro.core import approx
    num = _stated("kwt1-pallas")
    rng = np.random.default_rng(keys)
    s = (3 * rng.standard_normal((64, keys))).astype(np.float32)
    np.testing.assert_array_equal(
        reference.softmax_lut(s.astype(np.float64), num["softmax_lut"]),
        np.asarray(approx.softmax_lut(jnp.asarray(s), fixed=True)))
    x = (2 * rng.standard_normal(4096)).astype(np.float32)
    np.testing.assert_array_equal(
        reference.gelu_lut(x.astype(np.float64), num["gelu_lut"]),
        np.asarray(approx.gelu_lut(jnp.asarray(x))))


def test_controls_are_one_step_below_the_stated_numerics():
    num = reference.Numerics.stated(_stated("kwt-tiny-pallas"))
    assert (num.attention_operands, num.float_dtype) == ("bfloat16",
                                                         "float32")
    assert num.lowered("attention").attention_operands == "float8_e4m3fn"
    assert num.lowered("float").float_dtype == "bfloat16"
    grid = num.lowered("grid")
    assert (grid.bits, grid.weight_exponent, grid.input_exponent) == (4, 2, 1)
    x = np.array([1 / 3, 300.0, 1e6])
    assert reference.round_to(x, "bfloat16")[0] == 0.333984375
    np.testing.assert_array_equal(reference.round_to(x, "float8_e4m3fn"),
                                  [0.34375, 288.0, 448.0])


# -- whole runs on the CPU -------------------------------------------------------

@pytest.fixture(scope="module")
def cpu_root(tmp_path_factory):
    """The benchmark with the matmul operands it states on the CPU:
    XLA:CPU multiplies float32 at full precision, where one pass of the
    TPU's default precision rounds the operands to bfloat16."""
    root = tmp_path_factory.mktemp("cpu")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    for path in (root / "bench" / "configs").glob("*.json"):
        conf = json.loads(path.read_text())
        for key in ("attention_operands", "frontend_operands",
                    "linear_operands"):
            if key in conf["numerics"]:
                conf["numerics"][key] = "float32"
        path.write_text(json.dumps(conf))
    return root


def _run(root, workload="tiny-audio", **kw):
    return harness.run(workload, SEED, 0.5, False, lanes=TEST_LANES,
                       require_chip=False, root=root, log=lambda s: None,
                       **kw)


def _fails(readings: dict, checks: dict) -> bool:
    return any(v > checks[k]["limit"] for k, v in readings.items()
               if k in checks)


def test_a_sound_run_is_correct_and_reports_its_metrics(cpu_root):
    result, checks = _run(cpu_root)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "stream_capacity",
                                      "hop_p95_ms"}
    assert list(result)[-1] == "checks"
    assert checks["window_compiles"]["value"] == 0


@pytest.mark.parametrize("workload,fail", [
    ("tiny-audio", set(harness.CONTROLS)), ("kwt1-audio", {"grid"}),
    ("kwt1-float-audio", {"grid", "attention"})])
def test_each_control_is_not_correct(workload, fail):
    """The reference one precision step below the configuration's own
    numerics, in the grids (or the float plan's linear operands), the
    attention operands or the floats, in the program's place: each fails
    a number the cell compares, or, where the program's own rounding
    reads as far as a control's (KWT-1's 12 layers), the 4-bit grid and,
    on the float plan, fp8 attention do (PERF.md section 2)."""
    result, checks = _run(ROOT, workload, control=True)
    assert set(result["control"]) == set(harness.CONTROLS)
    failed = {part for part, readings in result["control"].items()
              if _fails(readings, checks)}
    assert failed == fail, (result["control"], checks)


def test_the_programs_own_4bit_weights_are_not_correct(cpu_root):
    result, checks = _run(cpu_root, program_bits=4)
    assert not result["correct"], checks


def test_a_sound_feature_ingest_run_is_correct(cpu_root):
    result, checks = _run(cpu_root, "tiny-feature")
    assert result["correct"], checks


def test_a_sound_float_plan_run_is_correct(cpu_root):
    result, checks = _run(cpu_root, "kwt1-float-audio")
    assert result["correct"], checks
    assert set(result["metrics"]) == {"setup_s", "stream_capacity",
                                      "hop_p95_ms"}


def _stale_state(orig):
    def hop(self, chunk, ingest=None):
        before = (self.state, self.dstate)
        events = orig(self, chunk, ingest)
        self.state, self.dstate = before
        return events
    return hop


def _half_batch(orig):
    def hop(self, chunk, ingest=None):
        events = orig(self, chunk, ingest)
        logits = events["logits"].copy()
        half = len(logits) // 2
        logits[half:] = logits[:len(logits) - half]
        return {**events, "logits": logits}
    return hop


def _altered_answer(orig):
    def hop(self, chunk, ingest=None):
        events = orig(self, chunk, ingest)
        return {**events, "logits": events["logits"][:, ::-1].copy()}
    return hop


@pytest.mark.parametrize("workload", ["tiny-audio", "kwt1-audio",
                                      "kwt1-float-audio"])
@pytest.mark.parametrize("fault", [_stale_state, _half_batch,
                                   _altered_answer])
def test_a_broken_timed_path_is_not_correct(monkeypatch, cpu_root, fault,
                                            workload):
    from repro.cell import cell as cellmod
    monkeypatch.setattr(cellmod.StreamLanes, "hop",
                        fault(cellmod.StreamLanes.hop))
    result, checks = _run(cpu_root, workload)
    assert not result["correct"], checks
    assert checks["logit_error"]["value"] > checks["logit_error"]["limit"]


def test_a_traced_run_gives_its_readers_spans_counters_and_work(
        cpu_root, reader_contexts, v5e_peaks):
    """On the CPU too the profiler records the program's ``cell.hop``
    spans: a traced run hands its readers each phase's host seconds, the
    increase of the program's counters over the window and the work
    terms, and the four phase metrics together take no more than a
    step."""
    result, checks = harness.run("tiny-audio", SEED, 0.5, True,
                                 lanes=TEST_LANES, require_chip=False,
                                 root=cpu_root, log=lambda s: None)
    assert result["correct"], checks
    ctx = reader_contexts[0]
    red, steps, counted = ctx["trace"], ctx["steps"], ctx["counters"]
    assert set(red.program_s) == {spans.HOP_SPAN, *(
        f"{spans.HOP_SPAN}.{p}" for p in spans.PHASES)}
    assert counted["cell_hops_total"] == steps * TEST_LANES > 0
    assert counted["cell_hop_fetches_total"] == steps
    assert counted["cell_tokens_total"] == 0
    for p in spans.PHASES:
        assert counted[f'cell_hop_phase_seconds_total{{phase="{p}"}}'] > 0
    assert set(ctx["work"]) == {"step_flops", "encoder_flops",
                                "encoder_min_bytes"}
    phases = [result["metrics"][f"hop_{p}_ms"]["value"]
              for p in spans.PHASES]
    assert 0 < sum(phases) <= 1e3 * red.window_s / steps


# -- the entry point ---------------------------------------------------------------

def _entry(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny-audio-256",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_entry_point_refuses_a_cpu_platform():
    proc = _entry(ROOT)
    assert proc.returncode != 0
    assert "cpu" in proc.stderr
    assert "{" not in proc.stdout


def test_a_run_fails_with_the_benchmark_files_alone(tmp_path):
    """Without the program beside it a run stops before any result, past
    the look for a chip as well."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert _entry(tmp_path).returncode != 0
    code = ("import sys; sys.path.insert(0, 'bench'); "
            "from yardstick import harness; "
            "harness.run('tiny-audio-256', 1, 1, False, require_chip=False)")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=dict(env, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert "{" not in proc.stdout
