"""ServeCell: everything between a request and an Engine, on one host.

One cell owns, per host of the serving fleet:

* a swap-safe :class:`runtime.EngineHandle` (``cell.hotswap`` replaces
  the Engine under it without touching lane state),
* a pool of ``slots`` batch lanes — streaming-KWS lanes
  (:class:`StreamLanes`, the fused engine+detector hop) or LM request
  lanes (:class:`cell.scheduler.LMScheduler`, continuous batching),
* an :class:`cell.admission.AdmissionController` in front of the lanes,
* the ``cell_*`` metric bundle on the run's telemetry registry,
* optionally a :class:`cell.hotswap.CheckpointWatcher` on a directory
  where training publishes packed artifacts.

Entering the cell (``with cell:``) activates the host mesh and the
``dist.ctx`` data-parallel context, so every activation the lanes push
through ``stream_step`` / ``decode_step`` is sharded per-lane over the
mesh's DP axes (exact no-op on a single device).  Multi-host: run one
cell per host over that host's mesh slice; cells share nothing but the
checkpoint directory, which is how new weights propagate.

Both serve launchers (``launch/serve.py``, ``launch/stream_serve.py``)
are thin CLIs over this class.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro import telemetry
from repro.cell import admission as admission_mod
from repro.cell import hotswap as hotswap_mod
from repro.cell import pipeline as pipeline_mod
from repro.cell import scheduler as scheduler_mod
from repro.dist import ctx
from repro.launch import mesh as meshlib
from repro.stream import detector as det
from repro.stream import engine as stream_engine
from repro.telemetry import flight as flight_mod
from repro.telemetry.cell import (HOP_PHASE_SPANS, HOP_PHASES, HOP_SPAN,
                                  make_cell_metrics)


class ServeCell:
    """One host's serving cell: EngineHandle + lanes + admission + swap."""

    def __init__(self, engine, *, slots: int,
                 registry: Optional[telemetry.Registry] = None,
                 admission: Optional[admission_mod.AdmissionConfig] = None,
                 watch_dir: Optional[str] = None,
                 watch_like: Any = None,
                 probe: Any = None,
                 flight: Any = None,
                 mesh=None, poll_s: float = 0.5):
        self.handle = engine if isinstance(engine, runtime.EngineHandle) \
            else runtime.EngineHandle(engine)
        self.slots = slots
        self.metrics = make_cell_metrics(registry if registry is not None
                                         else telemetry.default_registry())
        self.admission = admission_mod.AdmissionController(
            admission or admission_mod.AdmissionConfig(),
            metrics=self.metrics)
        self.watcher = None
        self._watch_like, self._probe = watch_like, probe
        if watch_dir is not None:
            assert watch_like is not None and probe is not None, \
                "a watching cell needs a restore template and a probe batch"
            self.watcher = hotswap_mod.CheckpointWatcher(watch_dir,
                                                         poll_s=poll_s)
        self.mesh = meshlib.make_host_mesh() if mesh is None else mesh
        self.metrics.engine_generation.set(self.handle.generation)
        # black box: ``flight`` is a FlightRecorder, a FlightConfig, or
        # True for defaults; every lane hop feeds it (StreamLanes.hop)
        # and swap attempts re-check its triggers (maybe_swap).
        if flight is True:
            flight = flight_mod.FlightConfig()
        if isinstance(flight, flight_mod.FlightConfig):
            flight = flight_mod.FlightRecorder(self.metrics, flight)
        self.flight: Optional[flight_mod.FlightRecorder] = flight
        self._stack = None

    @property
    def engine(self) -> runtime.Engine:
        return self.handle.engine

    # -- mesh activation ---------------------------------------------------

    def __enter__(self) -> "ServeCell":
        assert self._stack is None, "cell already active"
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(
            ctx.mesh_context(self.mesh, meshlib.dp_axes(self.mesh)))
        return self

    def __exit__(self, *exc) -> None:
        stack, self._stack = self._stack, None
        stack.close()

    # -- lane pools --------------------------------------------------------

    def stream_lanes(self, fcfg, dcfg, *, chunk_hops: int = 1,
                     keep_features: bool = False,
                     pipelined: bool = False,
                     feature_ingest: bool = False) -> "StreamLanes":
        return StreamLanes(self, fcfg, dcfg, chunk_hops=chunk_hops,
                           keep_features=keep_features, pipelined=pipelined,
                           feature_ingest=feature_ingest)

    def lm_scheduler(self, *, max_len: int, eos_id: Optional[int] = None,
                     prefill_len: Optional[int] = None
                     ) -> scheduler_mod.LMScheduler:
        return scheduler_mod.LMScheduler(
            self.handle, slots=self.slots, max_len=max_len, eos_id=eos_id,
            prefill_len=prefill_len, metrics=self.metrics)

    # -- checkpoint hot-swap ----------------------------------------------

    def maybe_swap(self) -> bool:
        """One watch tick (call between hops): swap in a freshly published
        complete checkpoint, if any.  Never drops a lane — see
        ``cell.hotswap``."""
        if self.watcher is None:
            return False
        swapped = hotswap_mod.poll_and_swap(
            self.handle, self.watcher, self._watch_like, self._probe,
            metrics=self.metrics)
        if self.flight is not None:
            # a probe-parity failure bumps swap_failures; re-check the
            # triggers now instead of waiting for the next hop
            self.flight.check()
        return swapped


class StreamLanes:
    """``slots`` hop-synchronous audio lanes under one cell.

    Owns the engine + detector state pytrees and the per-lane lifecycle:
    ``join(lane)`` zeroes BOTH the stream state and the detector state of
    the lane (a recycled lane must not inherit the previous stream's
    hysteresis/refractory/warm-up — stream.detector), ``hop(chunk)``
    advances every lane by ``chunk_hops`` hops through the fused
    engine+detector step (or the split featurise/encode pipeline when
    ``pipelined``), ``evict(lane)`` frees it.

    Ingest modes: by default ``hop`` takes raw audio [B, chunk_samples]
    and the cell runs the MFCC frontend; with ``feature_ingest=True`` it
    takes pre-featurised frames [B, chunk_hops, F] — the deployment
    where edge devices featurise next to the microphone (as the paper's
    MCU target does) and the cell serves the encoder+detector.  Frames
    produced by ``features.frontend_push`` yield bit-identical scores on
    either path (tests/test_cell.py).

    Hop accounting: ``cell_hops_total`` counts hops ingested per ACTIVE
    lane — the quantity the soak reconciles against the offered source
    hops to assert zero drops across churn and hot-swaps.
    """

    def __init__(self, cell: ServeCell, fcfg, dcfg, *, chunk_hops: int = 1,
                 keep_features: bool = False, pipelined: bool = False,
                 feature_ingest: bool = False):
        eng = cell.engine
        assert eng.exec_cfg.family == "kwt", \
            "stream lanes drive the KWT family"
        assert not (pipelined and feature_ingest), \
            "feature ingest has no featurise stage to pipeline"
        self.cell, self.fcfg, self.dcfg = cell, fcfg, dcfg
        self.chunk_hops = chunk_hops
        self.feature_ingest = feature_ingest
        self.active = np.zeros(cell.slots, bool)
        cfg = eng.exec_cfg
        self.state = stream_engine.init_stream_state(
            cfg, fcfg, cell.slots, keep_features=keep_features)
        self.dstate = det.detector_init(dcfg, cell.slots)
        self._pipe = pipeline_mod.HopPipeline(
            cell.handle, fcfg, keep_features=keep_features, donate=False) \
            if pipelined else None

        def joint(params, state, dstate, chunk):
            if feature_ingest:
                state, logits = stream_engine.stream_step_frames(
                    params, state, chunk, cfg)
            else:
                state, logits = stream_engine.stream_step(params, state,
                                                          chunk, cfg, fcfg)
            dstate, events = det.detector_step(
                dstate, stream_engine.posteriors(logits), dcfg,
                warm=stream_engine.warm(state))
            return state, dstate, {**events, "logits": logits}

        self._joint = None if pipelined else jax.jit(joint)
        self._det = jax.jit(lambda ds, lg, warm: det.detector_step(
            ds, stream_engine.posteriors(lg), dcfg, warm=warm)) \
            if pipelined else None
        self._reset = jax.jit(lambda s, ds, lane: (
            stream_engine.reset_lane(s, lane),
            det.detector_reset_lane(ds, lane)))
        self._seq = 0                      # hops served (the span's seq)

    @property
    def chunk_samples(self) -> int:
        return self.chunk_hops * self.fcfg.hop_len

    def set_chunk_hops(self, k: int) -> None:
        """Adopt the admission controller's degrade signal.  Lane state is
        hop-count agnostic (rings advance per frame), so the width can
        change between steps; a new width compiles its own step variant."""
        self.chunk_hops = int(k)

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    def free_lanes(self) -> list[int]:
        return [i for i in range(len(self.active)) if not self.active[i]]

    def join(self, lane: int) -> None:
        """Claim a lane for a new stream: zero its ring/frontend/detector
        state so nothing leaks from the previous occupant."""
        assert not self.active[lane], f"lane {lane} is occupied"
        self.state, self.dstate = self._reset(self.state, self.dstate, lane)
        self.active[lane] = True
        m = self.cell.metrics
        m.joins.inc()
        m.occupancy.set(self.n_active / len(self.active))

    def evict(self, lane: int) -> None:
        assert self.active[lane], f"lane {lane} is already free"
        self.active[lane] = False
        m = self.cell.metrics
        m.evictions.inc()
        m.occupancy.set(self.n_active / len(self.active))

    def hop(self, chunk, ingest=None) -> dict:
        """Advance all lanes by ``chunk`` — raw audio
        [slots, chunk_samples], or pre-featurised frames
        [slots, chunk_hops, F] under ``feature_ingest``; returns
        the detector events ``{"fired": [B], "score": [B], ...}`` and
        the served ``"logits"`` [B, n_classes] (host numpy — the per-hop
        sync point, as in the pre-cell server).

        ``ingest`` ([slots] ints) overrides the per-lane hop accounting
        for steps whose trailing chunk is zero-padded past a stream's
        end (a degraded-width step need not divide the stream length);
        default: ``chunk_hops`` for every active lane.

        Each call is one ``cell.hop`` span with a child per host phase
        (``telemetry.cell.HOP_PHASES``); the phase times go to
        ``cell_hop_phase_seconds_total`` and the flight recorder, always,
        and the spans to the active tracer or profiler, when there is
        one."""
        m = self.cell.metrics
        clock = time.perf_counter_ns
        copy_in, dispatch, wait, copy_out = HOP_PHASE_SPANS
        t0 = clock()
        with telemetry.span(HOP_SPAN, {"seq": self._seq}):
            with telemetry.span(copy_in):
                bytes_in = chunk.nbytes
                chunk = jnp.asarray(chunk)
            t1 = clock()
            with telemetry.span(dispatch):
                events = self._dispatch(chunk)
            t2 = clock()
            with telemetry.span(wait):
                jax.block_until_ready(events)
            t3 = clock()
            with telemetry.span(copy_out):
                events = jax.tree.map(np.asarray, events)
            t4 = clock()
        self._seq += 1
        phase_ns = (t1 - t0, t2 - t1, t3 - t2, t4 - t3)
        for name, ns in zip(HOP_PHASES, phase_ns):
            m.hop_phase_s[name].inc(ns * 1e-9)
        m.hop_bytes_in.inc(bytes_in)
        m.hop_bytes_out.inc(sum(a.nbytes for a in events.values()))
        dur_ms = (t4 - t0) * 1e-6
        m.hop_ms.observe(dur_ms)
        m.hops.inc(int(np.sum(ingest)) if ingest is not None
                   else self.chunk_hops * self.n_active)
        if self.cell.flight is not None:
            self.cell.flight.record_hop(dur_ms, spans={
                name: ns * 1e-6 for name, ns in zip(HOP_PHASES, phase_ns)})
        return events

    def _dispatch(self, chunk) -> dict:
        """Enqueue one hop's programs on the device ``chunk``; returns
        the events and logits as device arrays, not yet computed."""
        p = self.cell.handle.live_params()
        if self._joint is not None:
            self.state, self.dstate, events = self._joint(
                p, self.state, self.dstate, chunk)
            return events
        self.state, window = self._pipe._feat(p, self.state, chunk)
        logits = self._pipe._enc(p, window)
        warm = self.state["embed"]["count"] >= \
            stream_engine.window_frames(self.cell.engine.exec_cfg)
        self.dstate, events = self._det(self.dstate, logits, warm)
        return {**events, "logits": logits}
