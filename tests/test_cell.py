"""repro.cell: continuous-batching join/evict bit-identity, admission
control, hop-pipeline parity, checkpoint hot-swap, and the satellite
hardening (serve_common crash flush, detector lane recycling, checkpoint
partial-write tolerance)."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import cell as cellmod
from repro import runtime
from repro import telemetry
from repro.cell import admission as admission_mod
from repro.checkpoint import manager
from repro.configs import registry
from repro.launch import serve_common
from repro.launch import steps
from repro.models import kwt
from repro.models import transformer
from repro.stream import detector as det
from repro.stream import engine as stream_engine
from repro.stream import features

FCFG = features.FrontendConfig()
HOP = FCFG.hop_len


@pytest.fixture(scope="module")
def lm_engine():
    cfg = registry.get("internlm2-1.8b").smoke
    params = steps.model_module(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return runtime.compile_model(cfg, params, backend="float")


@pytest.fixture(scope="module")
def kwt_setup():
    cfg = registry.get("kwt-tiny").smoke
    params = kwt.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _metrics():
    return telemetry.make_cell_metrics(telemetry.Registry())


# ---------------------------------------------------------------------------
# per-lane decode state (models.transformer)
# ---------------------------------------------------------------------------

def test_vector_index_decode_matches_scalar(lm_engine):
    """A per-lane [B] index at uniform depth must reproduce the scalar-
    index decode — the mechanism under continuous batching."""
    eng = lm_engine
    B = 2
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 6), 0,
                              eng.cfg.vocab_size)
    logits, s = eng.prefill(toks, eng.init_decode_state(B, 12))
    s_vec = {"layers": s["layers"],
             "index": jnp.broadcast_to(s["index"], (B,))}
    cur = jnp.argmax(logits, -1).astype(jnp.int32)
    cur_v = cur
    for _ in range(4):
        la, s = eng.decode_step(cur, s)
        lb, s_vec = eng.decode_step(cur_v, s_vec)
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=0, atol=0)
        cur = jnp.argmax(la, -1).astype(jnp.int32)
        cur_v = jnp.argmax(lb, -1).astype(jnp.int32)


def test_merge_decode_state_selects_per_lane(lm_engine):
    eng = lm_engine
    old = eng.init_decode_state(2, 8)
    new = eng.init_decode_state(2, 8)
    old["index"] = jnp.asarray([3, 5], jnp.int32)
    new["index"] = jnp.asarray([0, 0], jnp.int32)
    new["layers"] = jax.tree.map(
        lambda a: a + 1 if jnp.issubdtype(a.dtype, jnp.floating) else a,
        new["layers"])
    merged = transformer.merge_decode_state(old, new,
                                            jnp.asarray([False, True]))
    np.testing.assert_array_equal(np.asarray(merged["index"]), [3, 0])
    k = jax.tree.leaves(merged["layers"])[0]       # [n_layers, B, ...]
    assert float(jnp.sum(jnp.abs(k[:, 0].astype(jnp.float32)))) == 0.0
    assert float(jnp.sum(jnp.abs(k[:, 1].astype(jnp.float32)))) > 0.0


# ---------------------------------------------------------------------------
# LMScheduler: continuous batching
# ---------------------------------------------------------------------------

def _requests(cfg, n=5, seed=0):
    rng = np.random.RandomState(seed)
    return [(i, rng.randint(0, cfg.vocab_size, size=rng.randint(2, 12)),
             int(rng.randint(3, 10))) for i in range(n)]


def test_scheduler_order_invariant(lm_engine):
    """With a fixed prefill pad width, the schedule is invisible: any
    submission order yields bit-identical tokens per request."""
    reqs = _requests(lm_engine.cfg)

    def run(order):
        s = cellmod.LMScheduler(lm_engine, slots=2, max_len=64,
                                prefill_len=16)
        for j in order:
            rid, p, g = reqs[j]
            s.submit(rid, p, g)
        return s.run()

    a, b = run([0, 1, 2, 3, 4]), run([4, 3, 2, 1, 0])
    assert set(a) == set(b) == {0, 1, 2, 3, 4}
    for rid in a:
        assert a[rid] == b[rid]
        assert len(a[rid]) == reqs[rid][2]


def test_scheduler_preserves_residents_on_join(lm_engine):
    """THE continuous-batching property (and the launch/serve.py refill
    bug this subsystem fixes): a mid-flight join must not perturb a
    resident lane's decode — same tokens as an undisturbed run."""
    reqs = _requests(lm_engine.cfg)
    solo = cellmod.LMScheduler(lm_engine, slots=2, max_len=64,
                               prefill_len=16)
    solo.submit(0, reqs[0][1], reqs[0][2])
    want = solo.run()[0]

    s = cellmod.LMScheduler(lm_engine, slots=2, max_len=64, prefill_len=16)
    s.submit(0, reqs[0][1], reqs[0][2])
    out, n = {}, 0
    while not s.idle():
        if n == 2:                       # joiner lands mid-decode
            s.submit(1, reqs[1][1], reqs[1][2])
        for ev in s.step():
            out.setdefault(ev.rid, []).append(ev.token)
        n += 1
    assert out[0] == want
    assert len(out[1]) == reqs[1][2]


def test_scheduler_eos_evicts_early(lm_engine):
    s = cellmod.LMScheduler(lm_engine, slots=2, max_len=64, prefill_len=16)
    s.submit(0, [1, 2, 3], 40)
    evs = []
    while not s.idle():
        evs += s.step()
    # rerun with the first emitted token as EOS: must stop at one token
    eos = evs[0].token
    s2 = cellmod.LMScheduler(lm_engine, slots=2, max_len=64, prefill_len=16,
                             eos_id=eos)
    s2.submit(0, [1, 2, 3], 40)
    out = []
    while not s2.idle():
        out += s2.step()
    assert len(out) == 1 and out[0].done and out[0].reason == "eos"


def test_scheduler_metrics_ledger(lm_engine):
    met = _metrics()
    s = cellmod.LMScheduler(lm_engine, slots=2, max_len=64, prefill_len=16,
                            metrics=met)
    reqs = _requests(lm_engine.cfg, n=3)
    for rid, p, g in reqs:
        s.submit(rid, p, g)
    out = s.run()
    assert met.joins.value == 3 and met.evictions.value == 3
    assert met.tokens.value == sum(len(v) for v in out.values())
    assert met.prefill_tokens.value == sum(len(p) for _, p, _ in reqs)


def test_scheduler_rejects_recurrent_families():
    """rwkv/hybrid fold pad tokens irreversibly into recurrence state —
    they keep the drain-batch serve path."""
    fake = types.SimpleNamespace(
        exec_cfg=types.SimpleNamespace(family="rwkv"))
    with pytest.raises(AssertionError, match="dense/moe"):
        cellmod.LMScheduler(fake, slots=2, max_len=8)


def test_scheduler_rejects_oversized_request(lm_engine):
    s = cellmod.LMScheduler(lm_engine, slots=2, max_len=16)
    with pytest.raises(AssertionError):
        s.submit(0, list(range(10)), 8)          # 9 + 8 > 16


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_admission_bounded_queue():
    met = _metrics()
    a = admission_mod.AdmissionController(
        admission_mod.AdmissionConfig(max_queue=2), metrics=met)
    assert a.offer("s0").admitted and a.offer("s1").admitted
    d = a.offer("s2")
    assert not d.admitted and d.reason == "queue_full"
    assert met.admitted.value == 2 and met.rejected.value == 1
    assert a.pop() == "s0" and len(a) == 1


def test_admission_token_bucket():
    clk = _Clock()
    a = admission_mod.AdmissionController(
        admission_mod.AdmissionConfig(max_queue=100, rate=2.0, burst=2),
        clock=clk)
    assert a.offer(0).admitted and a.offer(1).admitted
    assert a.offer(2).reason == "rate"           # bucket drained
    clk.t += 0.5                                 # refills one token
    assert a.offer(3).admitted
    assert not a.offer(4).admitted


def test_admission_deadline_shed():
    clk = _Clock()
    met = _metrics()
    a = admission_mod.AdmissionController(
        admission_mod.AdmissionConfig(max_queue=10, deadline_ms=100.0),
        metrics=met, clock=clk)
    a.offer("stale")
    clk.t += 0.2                                 # 200 ms > deadline
    a.offer("fresh")
    assert a.pop() == "fresh"                    # stale one was shed
    assert met.rejected.value == 1


def test_admission_degrades_before_rejecting():
    clk = _Clock()
    met = _metrics()
    cfg = admission_mod.AdmissionConfig(max_queue=4, degrade_queue=2,
                                        degraded_chunk_hops=4,
                                        deadline_ms=1000.0)
    a = admission_mod.AdmissionController(cfg, metrics=met, clock=clk)
    a.offer(0)
    a.offer(1)
    assert a.chunk_hops() == 1                   # within bounds
    a.offer(2)                                   # queue depth 3 > 2
    assert a.chunk_hops() == 4                   # degraded, nothing shed
    assert met.degraded.value == 1 and met.rejected.value == 0
    a.offer(3)
    assert not a.offer(4).admitted               # only now: reject
    for _ in range(4):
        a.pop()
    assert a.chunk_hops() == 1                   # drained: recovers


# ---------------------------------------------------------------------------
# hop pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["float", "lut"])
def test_pipeline_split_matches_fused(kwt_setup, backend):
    """The featurise/encode split reproduces the fused stream_step logits
    bit-for-bit (the barrier seam is the split point), and the pipelined
    generator reproduces the synchronous split path."""
    cfg, params = kwt_setup
    eng = runtime.compile_model(cfg, params, backend=backend)
    pipe = cellmod.HopPipeline(eng, FCFG)
    rng = np.random.RandomState(0)
    chunks = [rng.randn(2, HOP).astype(np.float32) * 0.1 for _ in range(5)]

    s_fused = stream_engine.init_stream_state(cfg, FCFG, 2,
                                              keep_features=False)
    s_split = pipe.init_state(2)
    sync = []
    for c in chunks:
        s_fused, l_f = eng.stream_step(s_fused, jnp.asarray(c), FCFG)
        s_split, l_s = pipe.step(s_split, c)
        np.testing.assert_array_equal(np.asarray(l_f), np.asarray(l_s))
        sync.append(np.asarray(l_s))
    piped = [np.asarray(l) for _, l in pipe.run(pipe.init_state(2), chunks)]
    assert len(piped) == len(sync)
    for a, b in zip(sync, piped):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# hot-swap
# ---------------------------------------------------------------------------

def _packed(cfg, seed):
    """A packed int8 QTensor tree — the deploy artifact hot_swap loads."""
    params = kwt.init_params(cfg, jax.random.PRNGKey(seed))
    return runtime.QuantRecipe.from_config(cfg).quantize(params)


def test_hot_swap_parity_gate_and_generation(kwt_setup):
    cfg, _ = kwt_setup
    eng = runtime.compile_model(cfg, _packed(cfg, 0), backend="lut")
    assert eng.int_resident
    handle = runtime.EngineHandle(eng)
    probe = jnp.asarray(np.random.RandomState(1).randn(
        1, *cfg.input_dim).astype(np.float32))
    before = np.asarray(handle.engine.forward(probe))
    lp0 = handle.live_params()
    assert handle.live_params() is lp0           # cached per generation

    met = _metrics()
    q2 = _packed(cfg, 7)
    old = cellmod.hot_swap(handle, q2, probe, metrics=met)
    assert old is eng and handle.generation == 1
    assert met.swaps.value == 1 and met.swap_failures.value == 0
    after = np.asarray(handle.engine.forward(probe))
    assert not np.array_equal(before, after)
    # the deploy gate's own criterion, re-checked from outside: the
    # installed integer-executing plan reproduces a fresh same-flavour
    # compile of the artifact bit-for-bit, and stays within the
    # activation-quant envelope of the dequantise-first reference
    assert handle.engine.int_exec
    same = runtime.compile_model(cfg, q2, backend="lut")
    np.testing.assert_array_equal(after, np.asarray(same.forward(probe)))
    ref = runtime.compile_model(cfg, q2, backend="lut",
                                integer_resident=False, integer_exec=False)
    np.testing.assert_allclose(after, np.asarray(ref.forward(probe)),
                               atol=cellmod.hotswap._INT_EXEC_PROBE_TOL)
    assert handle.live_params() is not lp0       # cache invalidated


def test_hot_swap_strict_rejects_exec_mismatch(kwt_setup):
    cfg, params = kwt_setup
    handle = runtime.EngineHandle(
        runtime.compile_model(cfg, params, backend="float"))
    other = runtime.compile_model(cfg, params, backend="lut")
    with pytest.raises(ValueError, match="exec config"):
        handle.swap(other)
    assert handle.generation == 0                # untouched


def test_watcher_and_poll_and_swap(kwt_setup, tmp_path):
    cfg, _ = kwt_setup
    like = _packed(cfg, 0)
    handle = runtime.EngineHandle(
        runtime.compile_model(cfg, like, backend="lut"))
    probe = jnp.zeros((1,) + tuple(cfg.input_dim), jnp.float32)
    w = cellmod.CheckpointWatcher(str(tmp_path))
    assert w.poll() is None
    assert not cellmod.poll_and_swap(handle, w, like, probe)
    manager.save(str(tmp_path), 5, _packed(cfg, 3))
    assert w.poll() == 5
    assert cellmod.poll_and_swap(handle, w, like, probe)
    assert handle.generation == 1 and w.last_step == 5
    assert not cellmod.poll_and_swap(handle, w, like, probe)  # consumed


def test_watcher_wait_timeout_injected_clock(tmp_path):
    t = {"now": 0.0}
    slept = []

    def sleep(s):
        slept.append(s)
        t["now"] += s

    w = cellmod.CheckpointWatcher(str(tmp_path), poll_s=0.25,
                                  clock=lambda: t["now"], sleep=sleep)
    assert w.wait_for_new_step(timeout_s=1.0) is None
    assert slept and t["now"] >= 1.0


# ---------------------------------------------------------------------------
# checkpoint manager: latest-step discovery under partial writes
# ---------------------------------------------------------------------------

def test_latest_step_skips_partial_writes(tmp_path):
    d = str(tmp_path)
    manager.save(d, 3, {"w": jnp.ones((2,))})
    # in-flight tmp dir (pre-rename crash leftover)
    os.makedirs(os.path.join(d, "step_00000009.tmp-abcd1234"))
    # renamed but manifest-less (external partial copy)
    os.makedirs(os.path.join(d, "step_00000007"))
    # manifest present but payload shard missing
    os.makedirs(os.path.join(d, "step_00000008"))
    with open(os.path.join(d, "step_00000008", "manifest.json"), "w") as f:
        json.dump({"step": 8}, f)
    # corrupt (truncated) manifest
    os.makedirs(os.path.join(d, "step_00000011"))
    with open(os.path.join(d, "step_00000011", "manifest.json"), "w") as f:
        f.write('{"step": 11')
    # unparsable names must not crash the watcher
    os.makedirs(os.path.join(d, "step_garbage"))
    open(os.path.join(d, "step_"), "w").close()
    assert manager.latest_step(d) == 3
    manager.save(d, 12, {"w": jnp.ones((2,))})
    assert manager.latest_step(d) == 12


def test_latest_step_missing_dir():
    assert manager.latest_step("/nonexistent/ckpts") is None


# ---------------------------------------------------------------------------
# detector lane recycling (satellite)
# ---------------------------------------------------------------------------

def test_recycled_lane_must_not_inherit_detector_state():
    """Skipping the evict/join reset hands the next stream the previous
    one's refractory countdown and hysteresis latch — its own early
    keyword is silently suppressed.  The reset restores symmetry."""
    dcfg = det.DetectorConfig(smooth_hops=2, on_threshold=0.6,
                              off_threshold=0.4, refractory_hops=50)
    hot = jnp.asarray([[0.1, 0.9]])              # keyword-like posterior
    state = det.detector_init(dcfg, 1)
    fired_hops = []
    for _ in range(4):
        state, ev = det.detector_step(state, hot, dcfg)
        fired_hops.append(bool(ev["fired"][0]))
    assert any(fired_hops)                       # first stream fired

    # stream ends; lane recycled WITHOUT reset: the inherited hysteresis
    # latch + refractory suppress the new stream's identical keyword
    leaked = state
    for _ in range(4):
        leaked, ev = det.detector_step(leaked, hot, dcfg)
        assert not bool(ev["fired"][0])

    # with the reset, the new stream behaves exactly like the first one
    clean = det.detector_reset_lane(state, 0)
    fired2 = []
    for _ in range(4):
        clean, ev = det.detector_step(clean, hot, dcfg)
        fired2.append(bool(ev["fired"][0]))
    assert fired2 == fired_hops


def test_detector_reset_lane_accepts_index_array():
    dcfg = det.DetectorConfig()
    state = det.detector_init(dcfg, 4)
    state = {**state, "cooldown": state["cooldown"] + 9}
    state = det.detector_reset_lane(state, jnp.asarray([1, 3]))
    np.testing.assert_array_equal(np.asarray(state["cooldown"]),
                                  [9, 0, 9, 0])


# ---------------------------------------------------------------------------
# ServeCell + StreamLanes
# ---------------------------------------------------------------------------

def test_stream_lanes_lifecycle_and_ledger(kwt_setup):
    cfg, params = kwt_setup
    cell = cellmod.ServeCell(
        runtime.compile_model(cfg, params, backend="float"),
        slots=2, registry=telemetry.Registry())
    rng = np.random.RandomState(0)
    with cell:
        lanes = cell.stream_lanes(FCFG, det.DetectorConfig())
        lanes.join(0)
        lanes.join(1)
        with pytest.raises(AssertionError):
            lanes.join(0)                        # occupied
        for _ in range(3):
            lanes.hop(rng.randn(2, HOP).astype(np.float32))
        lanes.evict(1)
        lanes.hop(rng.randn(2, HOP).astype(np.float32))
        # partial trailing chunk: explicit per-lane ingest override
        lanes.hop(np.zeros((2, HOP), np.float32),
                  ingest=np.asarray([1, 0]))
        m = cell.metrics
        assert m.joins.value == 2 and m.evictions.value == 1
        assert m.hops.value == 3 * 2 + 1 + 1
        assert m.dropped_hops.value == 0
        assert lanes.free_lanes() == [1]


def test_stream_lanes_pipelined_matches_joint(kwt_setup):
    cfg, params = kwt_setup
    eng = runtime.compile_model(cfg, params, backend="float")
    cell = cellmod.ServeCell(eng, slots=2, registry=telemetry.Registry())
    rng = np.random.RandomState(2)
    with cell:
        a = cell.stream_lanes(FCFG, det.DetectorConfig())
        b = cell.stream_lanes(FCFG, det.DetectorConfig(), pipelined=True)
        for lanes in (a, b):
            lanes.join(0)
            lanes.join(1)
        for _ in range(4):
            c = rng.randn(2, HOP).astype(np.float32)
            ea, eb = a.hop(c), b.hop(c)
            np.testing.assert_array_equal(ea["score"], eb["score"])
            np.testing.assert_array_equal(ea["fired"], eb["fired"])


def test_stream_lanes_feature_ingest_matches_audio(kwt_setup):
    """Edge-featurised ingest: feeding the frames ``frontend_push``
    produces for a chunk is bit-identical to handing the cell the raw
    audio — the contract that lets edge devices own the MFCC stage."""
    cfg, params = kwt_setup
    eng = runtime.compile_model(cfg, params, backend="float")
    cell = cellmod.ServeCell(eng, slots=2, registry=telemetry.Registry())
    rng = np.random.RandomState(4)
    with cell:
        with pytest.raises(AssertionError):
            cell.stream_lanes(FCFG, det.DetectorConfig(),
                              feature_ingest=True, pipelined=True)
        a = cell.stream_lanes(FCFG, det.DetectorConfig())
        f = cell.stream_lanes(FCFG, det.DetectorConfig(),
                              feature_ingest=True)
        for lanes in (a, f):
            lanes.join(0)
            lanes.join(1)
        edge = features.frontend_init(FCFG, 2)  # the device-side frontend
        push = jax.jit(lambda s, c: features.frontend_push(s, c, FCFG))
        for _ in range(4):
            c = rng.randn(2, HOP).astype(np.float32)
            edge, frames = push(edge, c)
            ea, ef = a.hop(c), f.hop(frames)
            np.testing.assert_array_equal(ea["score"], ef["score"])
            np.testing.assert_array_equal(ea["fired"], ef["fired"])


# ---------------------------------------------------------------------------
# StreamLanes.hop: spans and phase counters
# ---------------------------------------------------------------------------

def _joined_lanes(kwt_setup, **cell_kw):
    cfg, params = kwt_setup
    cell = cellmod.ServeCell(
        runtime.compile_model(cfg, params, backend="float"), slots=2,
        registry=telemetry.Registry(), **cell_kw)
    lanes = cell.stream_lanes(FCFG, det.DetectorConfig())
    lanes.join(0)
    lanes.join(1)
    return cell, lanes


def _chunks(n, seed=5):
    rng = np.random.RandomState(seed)
    return [rng.randn(2, HOP).astype(np.float32) for _ in range(n)]


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_stream_hop_spans_reach_the_profiler_trace(kwt_setup, tmp_path):
    """Under the JAX profiler, with no tracer installed, every hop writes
    ``cell.hop`` (args: seq) with its four phase children, nested and in
    order, into the trace's host plane."""
    from jax.profiler import ProfileData

    from repro.telemetry.cell import HOP_PHASE_SPANS, HOP_SPAN
    cell, lanes = _joined_lanes(kwt_setup)
    with cell:
        lanes.hop(_chunks(1)[0])                     # compile outside
        assert telemetry.active_tracer() is None
        jax.profiler.start_trace(str(tmp_path))
        try:
            for c in _chunks(3):
                lanes.hop(c)
        finally:
            jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
               dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(HOP_SPAN)]
    hops = sorted(e for e in events if e[2] == HOP_SPAN)
    assert [e[3]["seq"] for e in hops] == [1, 2, 3]
    for h0, h1, _, _ in hops:
        inside = sorted(e for e in events
                        if e[2] != HOP_SPAN and h0 <= e[0] and e[1] <= h1)
        assert tuple(e[2] for e in inside) == HOP_PHASE_SPANS
        for prev, nxt in zip(inside, inside[1:]):
            assert prev[1] <= nxt[0]                 # in order, no overlap
    assert len(events) == 3 * (1 + len(HOP_PHASE_SPANS))


def test_stream_hop_spans_under_a_tracer(kwt_setup):
    """With a Tracer active the same names are Chrome events, the phases
    children of ``cell.hop``, and they account for its wall time."""
    from repro.telemetry.cell import HOP_PHASE_SPANS, HOP_SPAN
    cell, lanes = _joined_lanes(kwt_setup)
    with cell:
        lanes.hop(_chunks(1)[0])
        with telemetry.tracing() as tr:
            for c in _chunks(3):
                lanes.hop(c)
    hops = [e for e in tr.events if e["name"] == HOP_SPAN]
    assert [e["args"]["seq"] for e in hops] == [1, 2, 3]
    kids = [e["name"] for e in tr.events
            if e.get("args", {}).get("parent") == HOP_SPAN]
    assert kids == list(HOP_PHASE_SPANS) * 3
    assert telemetry.span_coverage(tr, HOP_SPAN) >= 0.9


def test_stream_hop_phase_and_transfer_counters(kwt_setup):
    """The always-on counters: the four phases sum to the hop's own wall
    time (no more than the caller saw), and the bytes moved in and out
    are the chunks' and the returned events'."""
    cell, lanes = _joined_lanes(kwt_setup)
    chunks = _chunks(5)
    wall, out_bytes = 0.0, 0
    with cell:
        for c in chunks:
            t = time.perf_counter()
            events = lanes.hop(c)
            wall += time.perf_counter() - t
            out_bytes += sum(np.asarray(a).nbytes for a in events.values())
    m = cell.metrics
    phase_s = sum(c.value for c in m.hop_phase_s.values())
    assert set(m.hop_phase_s) == {"copy_in", "dispatch", "wait", "copy_out"}
    assert all(c.value > 0 for c in m.hop_phase_s.values())
    assert phase_s <= wall
    assert phase_s == pytest.approx(1e-3 * sum(m.hop_ms.values()), rel=1e-9)
    assert m.hop_bytes_in.value == len(chunks) * chunks[0].nbytes
    assert m.hop_bytes_out.value == out_bytes
    prom = cell.metrics.hop_phase_s["wait"].to_prometheus()
    assert 'cell_hop_phase_seconds_total{phase="wait"}' in prom


def test_flight_dump_attributes_measured_hop_phases(kwt_setup, tmp_path,
                                                    monkeypatch):
    """A cell built with ``flight=True`` attributes its slow hops by the
    phases each hop measured, not by a static cost model."""
    from repro.telemetry.cell import HOP_PHASES
    monkeypatch.chdir(tmp_path)
    cell, lanes = _joined_lanes(kwt_setup, flight=True)
    cell.metrics.latency_budget.set(1e-9)            # every hop burns
    with cell:
        for c in _chunks(cell.flight.cfg.min_hops):
            lanes.hop(c)
    assert len(cell.flight.dumps) == 1
    with open(cell.flight.dumps[0]) as f:
        att = json.load(f)["attribution"]
    assert att["method"] == "measured-spans"
    assert set(att["stage_ms"]) == set(HOP_PHASES)
    assert att["slowest_stage"] in HOP_PHASES


def test_cell_swap_under_streaming_drops_nothing(kwt_setup, tmp_path):
    """Hot-swap between hops: lanes keep their ring positions, the hop
    ledger stays exact, and the post-swap engine serves the new params."""
    cfg, _ = kwt_setup
    like = _packed(cfg, 0)
    probe = jnp.zeros((1,) + tuple(cfg.input_dim), jnp.float32)
    cell = cellmod.ServeCell(
        runtime.compile_model(cfg, like, backend="lut"), slots=2,
        registry=telemetry.Registry(), watch_dir=str(tmp_path),
        watch_like=like, probe=probe)
    rng = np.random.RandomState(3)
    n_hops = 6
    with cell:
        lanes = cell.stream_lanes(FCFG, det.DetectorConfig())
        lanes.join(0)
        lanes.join(1)
        for h in range(n_hops):
            if h == 2:
                manager.save(str(tmp_path), 1, _packed(cfg, 9))
            assert cell.maybe_swap() == (h == 2)
            lanes.hop(rng.randn(2, HOP).astype(np.float32))
        m = cell.metrics
        assert cell.handle.generation == 1 and m.swaps.value == 1
        assert m.hops.value == n_hops * 2 and m.dropped_hops.value == 0
        # the embed ring advanced continuously across the swap
        want = min(n_hops, stream_engine.window_frames(cfg))
        assert int(lanes.state["embed"]["count"][0]) == want


def test_cell_watcher_requires_template_and_probe(kwt_setup):
    cfg, params = kwt_setup
    eng = runtime.compile_model(cfg, params, backend="float")
    with pytest.raises(AssertionError):
        cellmod.ServeCell(eng, slots=1, registry=telemetry.Registry(),
                          watch_dir="/tmp/nowhere")


# ---------------------------------------------------------------------------
# serve_common: crash-faithful telemetry flush (satellite)
# ---------------------------------------------------------------------------

def test_session_flushes_on_exception(tmp_path, capsys):
    out = str(tmp_path / "trace.json")
    with pytest.raises(RuntimeError, match="boom"):
        with serve_common.session(out) as (tracer, met):
            met.counter("serve_test_total").inc(3)
            with telemetry.span("doomed"):
                pass
            raise RuntimeError("boom")
    assert os.path.exists(out)
    assert os.path.exists(str(tmp_path / "trace.prom"))
    with open(str(tmp_path / "trace.metrics.json")) as f:
        assert json.load(f)["serve_test_total"]["value"] == 3
    assert "aborted=RuntimeError" in capsys.readouterr().out


def test_session_flushes_on_keyboard_interrupt(tmp_path):
    out = str(tmp_path / "trace.json")
    with pytest.raises(KeyboardInterrupt):
        with serve_common.session(out):
            raise KeyboardInterrupt
    assert os.path.exists(out)
    assert os.path.exists(str(tmp_path / "trace.metrics.json"))


def test_session_isolates_artifact_save_failures(tmp_path, monkeypatch):
    """A failing trace write must not eat the metric exports."""
    out = str(tmp_path / "trace.json")
    monkeypatch.setattr(
        telemetry.Tracer, "save",
        lambda self, p: (_ for _ in ()).throw(OSError("disk full")))
    with serve_common.session(out) as (tracer, met):
        met.gauge("serve_test_gauge").set(7.0)
    assert not os.path.exists(out)               # trace save failed...
    with open(str(tmp_path / "trace.metrics.json")) as f:   # ...metrics safe
        assert json.load(f)["serve_test_gauge"]["value"] == 7.0


def test_session_disabled_without_out_path():
    with serve_common.session(None) as (tracer, met):
        assert tracer is None
        assert telemetry.active_tracer() is None
