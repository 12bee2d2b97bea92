"""Tests of the harness's LM path and of the float KWT plan, on the CPU.

    python -m pytest bench/tests -q

The LM runs use a copy of the benchmark whose ``internlm2-chat`` cell
serves the registry's smoke configuration of InternLM2 (2 layers, width
64; the test hands it to the harness in place of the full one) at the
stated numerics (bfloat16), with short requests, so that a whole run
takes seconds.  Its weights are drawn with the published
standard deviation scaled to the smaller width, and twice that: at two
layers the lower-precision control then reads as far above the program
as it does at 24 layers on the chip (smoke: program at most 0.02,
control 1.4-1.8; chip: PERF.md section 2).
"""

from __future__ import annotations

import dataclasses
import json
import re
import shutil
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from yardstick import (harness, lm, lm_reference, lm_traffic,  # noqa: E402
                       lm_weights, reference, spec, trace, weights)

dense = spec.architecture("dense")

SEED = 2 ** 33 + 11
LM_CELL = "internlm2-chat"
LM_TRACE = BENCH / "tests" / "data" / "lm_smoke.xplane.pb"
SMOKE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab_size=256)
STREAM_E2E = {"setup_s", "stream_capacity", "hop_p95_ms"}
LM_E2E = {"setup_s", "tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}
LM_PER_LAYER = {"lm_mfu_pct", "prefill_share_pct", "decode_ms_per_step",
                "decode_roofline", "device_idle_pct.lm"}


def _conf(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def lm_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("lm")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    path = root / "bench" / "configs" / "internlm2-1.8b-bf16.json"
    conf = json.loads(path.read_text())
    conf["model"].update(SMOKE)
    conf["program_overrides"]["dtype"] = "the smoke variant's float32"
    conf["init_std"] *= 2 * (2048 / 64) ** 0.5
    path.write_text(json.dumps(conf))
    path = root / "bench" / "traffic" / "internlm2-chat.json"
    mix = json.loads(path.read_text())
    mix.update(slots=4, max_len=96, requests=256, block=16,
               prompt=dict(median=24, sigma=0.7, min=8, max=64),
               output=dict(median=8, sigma=0.7, min=2, max=32))
    path.write_text(json.dumps(mix))
    return root


@pytest.fixture
def lm_smoke(monkeypatch, lm_root):
    """``lm_root``, with the program's registry giving InternLM2's smoke
    configuration as its full one."""
    from repro.configs import registry
    get = registry.get

    def smoke_get(name):
        entry = get(name)
        return types.SimpleNamespace(config=entry.smoke) \
            if name == "internlm2-1.8b" else entry
    monkeypatch.setattr(registry, "get", smoke_get)
    return lm_root


def _lm_run(root, **kw):
    return harness.run(LM_CELL, SEED, 1.0, False, require_chip=False,
                       root=root, log=lambda s: None, **kw)


# -- the benchmark's files ---------------------------------------------------------

def test_load_cell_gives_each_path_only_its_metrics():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        layers = {m["name"] for m in cell.per_layer}
        if cell.config.get("path", "stream") == "lm":
            assert e2e == LM_E2E
            assert layers == LM_PER_LAYER
        else:
            assert e2e == STREAM_E2E
            assert not layers & LM_PER_LAYER and layers
    float_cell = spec.load_cell("kwt1-float-audio")
    assert {m["name"] for m in float_cell.per_layer} == {
        m["name"] for m in spec.load_cell("kwt1-audio").per_layer}


def test_the_lm_configuration_is_the_published_model():
    conf = _conf("internlm2-1.8b-bf16")
    pub, model = conf["published"], conf["model"]
    for ours, theirs in (("n_layers", "num_hidden_layers"),
                         ("d_model", "hidden_size"),
                         ("n_heads", "num_attention_heads"),
                         ("n_kv_heads", "num_key_value_heads"),
                         ("d_ff", "intermediate_size"),
                         ("vocab_size", "vocab_size"),
                         ("rope_theta", "rope_theta")):
        assert model[ours] == pub[theirs], ours
    assert model["head_dim"] * model["n_heads"] == pub["hidden_size"]
    assert model["tie_embeddings"] == pub["tie_word_embeddings"]
    assert conf["init_std"] == pub["initializer_range"]
    cfg = lm.program_config(conf)
    assert cfg.rope_theta == 1e6 and cfg.n_layers == 24


def test_a_model_key_the_program_disagrees_with_is_refused():
    conf = _conf("internlm2-1.8b-bf16")
    conf["model"]["d_ff"] = 4096
    with pytest.raises(ValueError, match="d_ff"):
        lm.program_config(conf)


def test_every_seed_gets_the_same_request_lengths():
    mix = spec.load_traffic(LM_CELL)
    a = lm_traffic.RequestBank(mix, 1, 92544)
    b = lm_traffic.RequestBank(mix, 2 ** 40 + 3, 92544)
    assert len(a) == mix["requests"] >= 4096
    block, slots = mix["block"], mix["slots"]
    np.testing.assert_array_equal(a.prompt_len, b.prompt_len)
    np.testing.assert_array_equal(a.out_len, b.out_len)
    assert not np.array_equal(a.tokens, b.tokens)
    # each block holds the distributions' quantiles, in its own order
    p_q = lm_traffic.quantile_lengths(mix["prompt"], block)
    o_q = lm_traffic.quantile_lengths(mix["output"], block)
    blocks = a.prompt_len.reshape(-1, block)
    assert not np.array_equal(blocks[1], blocks[2])
    for k in range(len(blocks)):
        np.testing.assert_array_equal(np.sort(blocks[k]), p_q)
    for k in range(1, len(blocks)):
        np.testing.assert_array_equal(
            np.sort(a.out_len[k * block:(k + 1) * block]), o_q)
    assert p_q.min() >= 32 and p_q.max() == 2048
    assert np.median(p_q) == pytest.approx(1020, rel=0.02)
    assert np.median(o_q) == pytest.approx(129, rel=0.03)
    assert o_q.min() >= 16 and o_q.max() <= 512
    # the requests already under way at the start are served a share of
    # what the first block's quantiles would give them
    whole = list(o_q)
    for n in a.out_len[slots:block]:
        whole.remove(n)
    assert len(whole) == slots
    assert a.out_len[:slots].max() <= max(whole)
    assert a.out_len[:slots].sum() < 0.6 * sum(whole)
    prompt, budget = a.request(5)
    assert prompt.dtype == np.int32 and len(prompt) == a.prompt_len[5]
    assert 0 <= prompt.min() and prompt.max() < 92544
    assert len(prompt) - 1 + budget <= mix["max_len"]
    # one warm-up prompt for each prefill width the bank needs, the
    # longest that needs it
    warm = a.warm_prompt_lengths()
    width = 1 << np.ceil(np.log2(a.prompt_len - 1)).astype(int)
    assert warm == sorted(int(a.prompt_len[width == w].max())
                          for w in np.unique(width))
    assert len(warm) == 3 and warm[-1] == 2048


def test_lm_flop_counts_equal_the_hand_counts():
    m = dict(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=2,
             d_ff=16, vocab_size=10)
    # per layer: q 8x8, k and v 8x4, out 8x8, gate/up/down 8x16
    assert dense.layer_weights(m) == 64 + 2 * 32 + 64 + 3 * 128
    one = 2 * 2 * 576                                   # two layers' matmuls
    # a token at position 3 attends to 4 keys: 2 * 2 * 4 * 8 per layer
    assert dense.tokens_flops(m, 3, 1, head=False) == one + 2 * 128
    assert dense.tokens_flops(m, 3, 1, head=True) == \
        one + 2 * 128 + 2 * 8 * 10
    # five prompt tokens at positions 0..4 attend to 1+2+3+4+5 keys
    assert dense.tokens_flops(m, 0, 5, head=False) == \
        5 * one + 2 * 2 * 2 * 15 * 8
    assert dense.tokens_flops(m, 0, 5, head=False) == sum(
        dense.tokens_flops(m, p, 1, head=False) for p in range(5))


def test_lm_least_bytes_count_valid_positions_only():
    m = dict(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=2,
             d_ff=16, vocab_size=10)
    weights_b = (2 * 576 + 80) * 2 + 5 * 8 * 4
    assert dense.weight_read_bytes(m, 2, 4) == weights_b
    kv_token = 2 * 2 * 4 * 2                  # layers x (K, V) x 4 x bf16
    lane = kv_token + 8 * 2 + 10 * 2          # new K/V, embed row, logits
    assert dense.decode_min_bytes(m, [3, 7], 2, 2, 4) == \
        weights_b + kv_token * 10 + 2 * lane
    # the same requests counted under a cache four times as long: the
    # counts are of real positions, not of the cache's length
    mix = dict(spec.load_traffic(LM_CELL), requests=128, block=64)
    conf = _conf("internlm2-1.8b-bf16")
    counts = []
    for max_len in (2560, 10240):
        bank = lm_traffic.RequestBank(dict(mix, max_len=max_len), 5, 92544)
        log = lm.Log(steps={0: [3, 4, 5], 1: [4, 5], ("warm", 0): [0]},
                     window=range(3, 6))
        counts.append(lm._window_work(dense, log, bank, conf["model"],
                                      conf["numerics"]))
    assert counts[0] == counts[1]
    prefill, decode, nbytes = counts[0]
    bank = lm_traffic.RequestBank(mix, 5, 92544)
    n0, n1 = (len(bank.request(i)[0]) for i in (0, 1))
    assert prefill == sum(dense.tokens_flops(conf["model"], 0, n - 1,
                                               False) for n in (n0, n1))
    assert decode[0] == dense.tokens_flops(conf["model"], n0 - 1, 1, True)
    assert nbytes[2] == dense.decode_min_bytes(
        conf["model"], [n0 + 1, n1], 2, 2, 4)


def _recorded_phases():
    red = trace.reduce_file(str(LM_TRACE))
    runs = red.module_runs
    decode = max((n for n in runs if runs[n] == max(runs.values())),
                 key=lambda n: red.module_s.get(n, 0.0))
    merges = [n for n in runs if n.startswith("jit_merge_decode_state")]
    return red, decode, merges


def test_lm_phases_on_a_recorded_chip_trace():
    """A traced window of the smoke cell (4 slots) recorded on a TPU v5e
    with the harness's spans: decode runs once a step, and the programs
    of the joins (prefill at each width, the fresh state, the merge) run
    once on each join step."""
    red, decode, merges = _recorded_phases()
    runs = red.module_runs
    assert decode.startswith("jit__lambda(")
    assert len(merges) == 1
    steps = int(runs[decode])
    ph = lm.phases(red, steps, int(runs[merges[0]]))
    assert ph["decode_runs"] == steps
    assert ph["decode"] == pytest.approx(red.module_s[decode])
    prefills = [n for n in runs if n.startswith("jit__lambda(")
                and n != decode]
    zeros = [n for n in runs if n.startswith("jit_broadcast_in_dim(")]
    assert ph["join"] == pytest.approx(sum(
        red.module_s.get(n, 0.0) for n in prefills + merges + zeros))
    assert ph["decode"] + ph["join"] <= red.busy_s * 1.0001


def _join_every_step(red, decode, steps):
    return red, steps, steps


def _prefill_every_step(red, decode, steps):
    runs = dict(red.module_runs)
    prefill = next(n for n in runs if n.startswith("jit__lambda(")
                   and n != decode)
    runs[prefill] = steps
    return dataclasses.replace(red, module_runs=runs), steps, 2


def _joins_miscounted(red, decode, steps):
    return red, steps, 5


def _unknown_program(red, decode, steps):
    name = "jit_join_into_live_state(123)"
    return dataclasses.replace(
        red, module_runs={**red.module_runs, name: 2},
        module_s={**red.module_s, name: 0.1 * red.busy_s}), steps, 2


@pytest.mark.parametrize("ambiguity", [_join_every_step,
                                       _prefill_every_step,
                                       _joins_miscounted, _unknown_program])
def test_lm_phases_refuse_a_trace_they_cannot_read(ambiguity):
    """Where the programs no longer fit the step as it was built (a join
    on every step, a second program run on every step, prefill runs that
    do not match the joins, a program of a new name that takes time),
    ``phases`` says so instead of labelling them by their run counts."""
    red, decode, _ = _recorded_phases()
    args = ambiguity(red, decode, int(red.module_runs[decode]))
    with pytest.raises(lm.Ambiguous):
        lm.phases(*args)
    ctx = {"phases": None, "trace": args[0]}
    for name in ("prefill_share_pct", "decode_ms_per_step"):
        assert spec.metric_reader(name)(ctx) is None


# -- the reference against the program ------------------------------------------------

def test_lm_reference_agrees_with_prefill_and_cached_decode():
    """Seeded random weights at the smoke size: the program's prefill of
    a prompt and then one cached decode step per token give the logits
    the reference's full forward gives at those positions."""
    import jax.numpy as jnp
    from repro import runtime
    from repro.configs import registry
    conf = _conf("internlm2-1.8b-bf16")
    model = dict(conf["model"], **SMOKE, dtype="float32")
    num = dict(conf["numerics"], weights="float32", activations="float32")
    cfg = registry.get("internlm2-1.8b").smoke.with_(rope_theta=1e6)
    params = lm_weights.make(SEED, dense.shapes(model), num, 0.1)
    eng = runtime.compile_model(cfg, params, backend="float")
    rng = np.random.default_rng(0)
    seq = rng.integers(0, 256, (2, 24), dtype=np.int32)
    state = eng.init_decode_state(2, 32)
    first, state = eng.prefill(jnp.asarray(seq[:, :16]), state)
    served = [np.asarray(first)]
    for t in range(16, 24):
        out, state = eng.decode_step(jnp.asarray(seq[:, t]), state)
        served.append(np.asarray(out))
    ref = dense.logits(params, seq, model,
                       lm_reference.Numerics.stated(num), "float32")
    np.testing.assert_allclose(np.stack(served, 1), ref[:, 15:],
                               rtol=2e-4, atol=2e-4)
    with pytest.raises(AssertionError):
        wrong = dense.logits(params, seq, dict(model, rope_theta=1e4),
                             lm_reference.Numerics.stated(num), "float32")
        np.testing.assert_allclose(np.stack(served, 1), wrong[:, 15:],
                                   rtol=2e-4, atol=2e-4)


def test_the_lm_weights_are_in_the_programs_layout():
    import jax
    from repro.configs import registry
    from repro.models import transformer
    conf = _conf("internlm2-1.8b-bf16")
    cfg = registry.get("internlm2-1.8b").config
    theirs = jax.eval_shape(lambda k: transformer.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    mine = jax.eval_shape(lambda: lm_weights.make(
        SEED, dense.shapes(conf["model"]), conf["numerics"], 0.02))
    lm_weights.check_layout(mine, theirs)
    total = sum(x.size for x in jax.tree.leaves(mine))
    assert 1.88e9 < total < 1.90e9                       # ~1.89 B parameters


def test_float_kwt_reference_matches_the_float_engine():
    """KWT-Tiny on the float backend against the reference's float mode
    (no grid, float32 operands: XLA:CPU multiplies float32 exactly)."""
    from repro import runtime
    from repro.configs import registry
    conf = _conf("kwt1-float")
    model = _conf("kwt-tiny-pallas")["model"]
    num = dict(conf["numerics"], linear_operands="float32",
               attention_operands="float32")
    params = weights.make(SEED, model)
    eng = runtime.compile_model(registry.get("kwt-tiny").config, params,
                                backend="float")
    x = np.random.default_rng(1).standard_normal((6, 16, 26)).astype(
        np.float32)
    prog = np.asarray(eng.forward(x))
    stated = reference.Numerics.stated(num)
    ref = reference.kwt_logits(weights.to_host(params), np.swapaxes(x, 1, 2),
                               model, stated)
    np.testing.assert_allclose(prog, ref, rtol=1e-4, atol=1e-5)
    low = reference.kwt_logits(weights.to_host(params), np.swapaxes(x, 1, 2),
                               model, stated.lowered("grid"))
    assert stated.lowered("grid").linear_operands == "bfloat16"
    assert np.max(np.abs(low - ref)) > 100 * np.max(np.abs(prog - ref))


# -- whole LM runs on the CPU ------------------------------------------------------------

def test_a_sound_lm_run_is_correct_and_reports_its_metrics(lm_smoke):
    result, checks = _lm_run(lm_smoke)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == LM_E2E
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert checks["window_compiles"]["value"] == 0
    assert set(checks) == {"token_gap", "window_compiles", "bad_requests"}


def test_the_lm_weight_control_is_not_correct(lm_smoke):
    """The reference with its weights one step below the stated type, in
    the program's place."""
    result, checks = _lm_run(lm_smoke, control=True)
    assert result["correct"]
    assert result["control"]["weights"]["token_gap"] > \
        checks["token_gap"]["limit"]


def _altered_token(monkeypatch):
    import jax.numpy as jnp
    from repro.runtime import engine
    orig = engine.Engine.decode_step

    def decode_step(self, token, state):
        logits, state = orig(self, token, state)
        return jnp.roll(logits, 1, axis=-1), state
    monkeypatch.setattr(engine.Engine, "decode_step", decode_step)


def _dropped_join_cache(monkeypatch):
    """The merge keeps the live state's cache for the joining lanes: the
    prompt's keys and values never reach them."""
    from repro.models import transformer
    orig = transformer.merge_decode_state

    def merge(old, new, lane_mask):
        merged = orig(old, new, lane_mask)
        return {**merged, "layers": old["layers"]}
    monkeypatch.setattr(transformer, "merge_decode_state", merge)


def _wrong_rope_theta(monkeypatch):
    return {"program_overrides": {"rope_theta": 1e4}}


@pytest.mark.parametrize("fault", [_altered_token, _dropped_join_cache,
                                   _wrong_rope_theta])
def test_a_broken_lm_timed_path_is_not_correct(monkeypatch, lm_smoke, fault):
    kw = fault(monkeypatch) or {}
    result, checks = _lm_run(lm_smoke, **kw)
    assert not result["correct"], checks
    assert checks["token_gap"]["value"] > checks["token_gap"]["limit"]


# -- the dense architecture reads as it did before it was a module ------------

PINNED = json.loads((BENCH / "tests" / "data" / "pinned_readings.json")
                    .read_text())


def _pinned_weights():
    conf = _conf("internlm2-1.8b-bf16")
    model = dict(conf["model"], **SMOKE)
    pin = PINNED["weights"]
    return model, conf["numerics"], lm_weights.make(
        pin["seed"], dense.shapes(model), conf["numerics"], pin["init_std"])


def test_the_dense_weights_are_the_pinned_ones_bit_for_bit():
    """InternLM2's weights at the smoke size, drawn through the
    architecture module's layout, hash as the harness drew them before
    (``data/pinned_readings.json``)."""
    import hashlib

    import jax
    _, _, params = _pinned_weights()
    got = {jax.tree_util.keystr(path): hashlib.sha256(
        np.asarray(leaf).tobytes()).hexdigest()
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == PINNED["weights"]["sha256"]


def test_the_dense_reference_is_the_pinned_one_bit_for_bit():
    """On those weights the reference's logits at the stated numerics,
    and the control's best, top and picked logits, equal those recorded
    before (``data/pinned_reference.npz``, XLA:CPU)."""
    model, num, params = _pinned_weights()
    pin = np.load(BENCH / "tests" / "data" / "pinned_reference.npz")
    seq = pin["tokens"]
    stated = lm_reference.Numerics.stated(num)
    np.testing.assert_array_equal(
        dense.logits(params, seq, model, stated, "bfloat16"), pin["logits"])
    best, top, picked = dense.evaluate(
        params, seq, np.stack([seq, np.roll(seq, 1, -1)]), model,
        stated.lowered(), "bfloat16")
    np.testing.assert_array_equal(best, pin["low_best"])
    np.testing.assert_array_equal(top, pin["low_top"])
    np.testing.assert_array_equal(picked, pin["low_picked"])


# -- an architecture brought by files alone -----------------------------------

RECORDING = '''"""A test architecture: the dense one, recording each call."""

from pathlib import Path

from yardstick import spec

dense = spec.architecture("dense", Path(__file__).resolve().parents[1])
calls = []


def _recorded(name):
    fn = getattr(dense, name)

    def call(*args, **kw):
        calls.append(name)
        return fn(*args, **kw)
    return call


shapes = _recorded("shapes")
evaluate = _recorded("evaluate")
logits = _recorded("logits")
tokens_flops = _recorded("tokens_flops")
decode_min_bytes = _recorded("decode_min_bytes")


def window_work(log, bank, model, numerics):
    calls.append("window_work")
    return {"served_tokens": sum(
        1 for steps in log.steps.values() for k in steps if k in log.window)}
'''

SERVED_TOKENS = '''def read(ctx):
    return ctx["work"]["served_tokens"] or None
'''


@pytest.fixture
def arch_root(lm_smoke, tmp_path):
    """``lm_smoke``, with files and entries added and none changed: an
    architecture module ``recording``, a configuration that names it, a
    cell of that configuration on ``internlm2-chat``'s mix, and a
    per-layer metric that reads a term of the module's ``window_work``."""
    root = tmp_path / "arch"
    shutil.copytree(lm_smoke, root,
                    ignore=shutil.ignore_patterns(harness.CACHE_DIR))
    bench = root / "bench"
    (bench / "arch" / "recording.py").write_text(RECORDING)
    (bench / "metrics" / "served_tokens.py").write_text(SERVED_TOKENS)
    conf = json.loads((bench / "configs" / "internlm2-1.8b-bf16.json")
                      .read_text())
    conf["architecture"] = "recording"
    (bench / "configs" / "recording-lm.json").write_text(json.dumps(conf))
    spec_ = json.loads((root / "BENCHMARK.json").read_text())
    spec_["configs"].append({"name": "recording-lm", "source": "test",
                             "file": "bench/configs/recording-lm.json",
                             "reduced": [], "why": "test"})
    spec_["workloads"].append({"name": "recording-chat",
                               "config": "recording-lm",
                               "traffic": "internlm2-chat", "chips": 1,
                               "why": "test"})
    for m in spec_["end_to_end"]:
        if LM_CELL in m.get("workloads", ()):
            m["workloads"].append("recording-chat")
    spec_["per_layer"].append({"name": "served_tokens", "unit": "tokens",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "tokens_per_s",
                               "workloads": ["recording-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec_))
    return root


def test_a_configuration_runs_through_the_architecture_it_names(
        arch_root, reader_contexts, v5e_peaks):
    """Weights, the reference, the FLOP and byte counts and the extra work
    terms of a traced run all come from the module the configuration
    names, and a metric added as a file reads its own term; the readers
    also get the window's counters and the trace's named and program
    time."""
    result, checks = harness.run("recording-chat", SEED, 1.0, True,
                                 require_chip=False, root=arch_root,
                                 log=lambda s: None)
    assert result["correct"], checks
    calls = set(spec.architecture("recording", arch_root / "bench").calls)
    assert {"shapes", "evaluate", "tokens_flops", "decode_min_bytes",
            "window_work"} <= calls
    ctx = reader_contexts[0]
    served = ctx["work"]["served_tokens"]
    # the closed loop keeps every slot busy, the one whose request under
    # way ended in set-up's step too: a token per slot and step
    slots = spec.load_traffic(LM_CELL, arch_root / "bench")["slots"]
    assert served == slots * ctx["steps"] > 0
    assert result["metrics"]["served_tokens"]["value"] == served
    assert {"model_flops", "decode_flops", "decode_min_bytes"} <= set(
        ctx["work"])
    # the program counted the tokens the harness saw served in the window
    assert ctx["counters"]["cell_tokens_total"] == served
    assert ctx["counters"]["cell_hops_total"] == 0
    assert isinstance(ctx["trace"].named_s, dict)
    assert isinstance(ctx["trace"].program_s, dict)


@pytest.mark.parametrize("name,error", [("no-such-model", FileNotFoundError),
                                        (None, ValueError)])
def test_an_lm_configuration_without_its_architecture_fails_at_load(
        tmp_path, name, error):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    path = tmp_path / "bench" / "configs" / "internlm2-1.8b-bf16.json"
    conf = json.loads(path.read_text())
    if name is None:
        del conf["architecture"]
    else:
        conf["architecture"] = name
    path.write_text(json.dumps(conf))
    want = str(tmp_path / "bench" / "arch" / "no-such-model.py") \
        if name else "architecture"
    with pytest.raises(error, match=re.escape(want)):
        spec.load_cell(LM_CELL, tmp_path)
