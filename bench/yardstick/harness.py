"""One run of one cell: set-up, the timed window, the check, the result.

A configuration names the serving path it runs on (``"path"``):
``stream`` (the default) or ``lm``, which :mod:`yardstick.lm` serves.

On the stream path the system under test is the program's stream cell:
weights go through ``runtime.compile_model`` into a ``ServeCell`` whose
``StreamLanes`` serve every lane; each step hands ``StreamLanes.hop`` a
fresh host chunk and takes back the detector events and logits on the
host.

:func:`run` returns the result line and the numbers compared, each with
its limit.  ``run.py`` prints them; the tests call :func:`run` with the
chip check off and with the timed path broken underneath.
"""

from __future__ import annotations

import contextlib
import dataclasses
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from yardstick import reference, spec, trace as trace_mod, work
from yardstick.traffic import Traffic, rng_for

HOP_SECONDS = 0.010                 # audio one lane-hop carries
CACHE_DIR = ".jax_cache"            # the compile cache, in the checkout
TRACE_SECONDS = 2.0                 # longest traced window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Window:
    steps: int = 0
    seconds: float = 0.0
    prep_seconds: float = 0.0
    latencies: list = dataclasses.field(default_factory=list)
    nonfinite: int = 0
    compiles: int = 0
    kept: dict = dataclasses.field(default_factory=dict)   # step -> logits


def check_device(chips: int):
    """The first device, and how many there are; ``NoChip`` unless they
    are TPUs and at least ``chips`` of them."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found platform {dev.platform!r} "
                     f"({dev.device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return dev, len(devices)


def _model_config(conf: dict):
    """The program's configuration for ``conf``, which has to agree with
    every size the file states, and with its integer grid where it states
    one."""
    from repro.configs import registry
    cfg = registry.get(conf["registry"]).config
    for key, want in conf["model"].items():
        have = getattr(cfg, key)
        have = list(have) if isinstance(have, tuple) else have
        if have != want:
            raise ValueError(f"{conf['registry']}: {key} is {have!r} in the "
                             f"program, {want!r} in the configuration file")
    num = conf["numerics"]
    if num["weight_bits"] is None:
        return cfg
    from repro.runtime.recipe import QuantRecipe
    recipe = QuantRecipe.from_config(cfg)
    for key, have in (("weight_bits", recipe.bits),
                      ("weight_exponent", recipe.weight_exponent),
                      ("input_exponent", recipe.input_exponent)):
        if have != num[key]:
            raise ValueError(f"{conf['registry']}: {key} is {have!r} in the "
                             f"program's recipe, {num[key]!r} in the "
                             "configuration file")
    return cfg


class _CompileCounter:
    """Counts traces and compilations while ``on``."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        self._cb = self._event
        jax.monitoring.register_event_duration_secs_listener(self._cb)

    def _event(self, name, _secs, **_kw):
        if self.on and name in COMPILE_EVENTS:
            self.count += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._cb)


def _timed_window(lanes, traffic, first_step, seconds, keep_lanes,
                  counter, annotate):
    """Hop every lane, a fresh host chunk each step, for ``seconds``."""
    import jax
    w = Window()
    span = jax.profiler.TraceAnnotation if annotate else \
        (lambda _name: contextlib.nullcontext())
    counter.on = True
    t0 = time.perf_counter()
    step = first_step
    with span("bench_window"):
        while time.perf_counter() - t0 < seconds:
            ta = time.perf_counter()
            with span("prepare_chunk"):
                chunk = traffic.chunk(step)
            tb = time.perf_counter()
            with span("hop"):
                events = lanes.hop(chunk)
            tc = time.perf_counter()
            logits = events["logits"]
            w.latencies.append(tc - tb)
            w.prep_seconds += tb - ta
            w.nonfinite += int(np.sum(~np.isfinite(logits).all(axis=-1)))
            w.kept[step] = np.array(logits[keep_lanes], np.float64)
            step += 1
    w.seconds = time.perf_counter() - t0
    counter.on = False
    w.compiles = counter.count
    w.steps = step - first_step
    return w


def _reference_inputs(traffic, conf, pairs, window_frames):
    """Model inputs [P, T, F] of the sampled (lane, step) pairs, by the
    reference frontend at the stated numerics for audio ingest."""
    fr = conf["frontend"]
    xs = [traffic.lane_input(lane, step, window_frames)
          for lane, step in pairs]
    if traffic.feature:
        return np.stack(xs).astype(np.float64)
    return reference.mfcc(np.stack(xs), fr,
                          conf["numerics"]["frontend_operands"])


def _sample_pairs(seed, keep_lanes, steps, n_pairs):
    """``n_pairs`` (lane, step) pairs drawn from the seed among the kept
    lanes and the window's steps; the last step is always among them."""
    rng = rng_for(seed, 4)
    all_pairs = [(lane, s) for s in steps for lane in keep_lanes]
    take = min(n_pairs, len(all_pairs))
    last = [p for p in all_pairs if p[1] == steps[-1]]
    rest = [p for p in all_pairs if p[1] != steps[-1]]
    idx = rng.choice(len(rest), size=max(0, take - len(last)), replace=False)
    return last[:take] + [rest[i] for i in sorted(idx)]


CONTROLS = ("grid", "attention", "float")
MATCH = 1e-4          # a lane-hop matches when every logit is this close


def logit_checks(served: np.ndarray, ref: np.ndarray) -> dict:
    """The numbers that compare served logits [P, C] with the reference's.

    ``logit_error``: RMS of the error over the RMS of the reference's
    logits.  (Over their spread about each row's mean instead, a random
    two-class model whose logits nearly tie would read ten times higher.)
    ``flip_rate``: how many rounding decisions per lane-hop went the
    other way from the reference's, as a Poisson count estimated from the
    share of lane-hops whose every logit lies within ``MATCH`` of that
    RMS: -ln(share), the share floored at half a lane-hop.  One int8
    rounding or table index that flips moves a lane-hop's logits as far
    as a lower precision everywhere does, so the size of the error
    cannot tell the two apart; how often a lane-hop escapes every flip
    can."""
    scale = np.sqrt(np.mean(ref ** 2))
    err = served - ref
    matched = np.sum(np.max(np.abs(err), axis=-1) <= MATCH * scale)
    return {"logit_error": float(np.sqrt(np.mean(err ** 2)) / scale),
            "flip_rate": float(-np.log(max(matched, 0.5) / len(ref)))}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = spec.ROOT, lanes: int | None = None,
        require_chip: bool = True, control: bool = False,
        program_bits: int | None = None,
        program_overrides: dict | None = None, record: dict | None = None,
        t_start: float | None = None, log=print) -> tuple[dict, dict]:
    """One run of ``workload``; returns ``(result, checks)``.

    ``lanes`` overrides the mix's lane count (the lane sweep), or on the
    LM path its slot count.
    ``control`` also computes the reference one precision step below the
    stated numerics in each part of :data:`CONTROLS` (the integer grids,
    the attention operands, the float intermediates), each put in the
    program's place on the same inputs: the controls, which have to fail
    the comparison; their readings go to ``result["control"]``.
    ``program_bits`` serves the program's own lower-precision weights
    (its recipe at that width, with the grid's range kept): the program's
    control, which has to fail the comparison too.
    ``record``, a dict, receives the logits compared (``served``, ``ref``
    and, with ``control``, ``control.<part>``), the (lane, step) pairs
    they belong to and the weights.
    On the LM path ``control`` computes the reference with its weights
    one precision step lower, ``program_overrides`` sets keys of the
    program's configuration apart from what the file states (a planted
    fault), and ``record`` receives the requests compared and their gaps.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.load_cell(workload, root)
    conf, mix = cell.config, cell.traffic
    path = conf.get("path", "stream")
    if path not in ("stream", "lm"):
        raise ValueError(f"unknown serving path {path!r}")
    import jax
    if require_chip:
        dev, count = check_device(cell.chips)
    else:
        dev, count = jax.devices()[0], len(jax.devices())

    # one fixed directory inside the checkout, so that only a cell's first
    # run in a checkout compiles.  Unlike runtime.enable_compile_cache this
    # ignores $JAX_COMPILATION_CACHE_DIR: a machine may point that at one
    # directory for every checkout, and two checkouts compared must not
    # share compiled programs
    jax.config.update("jax_compilation_cache_dir",
                      str(Path(root) / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if path == "lm":
        from yardstick import lm
        return lm.run(cell, seed, seconds, trace, dev=dev, count=count,
                      slots=lanes, control=control,
                      program_overrides=program_overrides, record=record,
                      t_start=t_start, log=log)

    n_lanes = int(lanes or mix["lanes"])
    from repro import cell as cellmod
    from repro import runtime
    from repro.stream import detector, features
    from yardstick import weights
    counter = _CompileCounter()
    try:
        cfg = _model_config(conf)
        model = conf["model"]
        fcfg = features.FrontendConfig(**conf["frontend"])
        t_frames = model["input_dim"][1]
        t_init = time.perf_counter()
        params = weights.make(seed, model)
        recipe = None
        if program_bits is not None:
            from repro.runtime.recipe import QuantRecipe
            cut = conf["numerics"]["weight_bits"] - program_bits
            recipe = QuantRecipe.from_config(cfg).with_(
                bits=program_bits,
                weight_exponent=conf["numerics"]["weight_exponent"] - cut)
        eng = runtime.compile_model(cfg, params, backend=conf["backend"],
                                    recipe=recipe)
        t_model = time.perf_counter()
        traffic = Traffic(mix, seed, n_lanes, conf["frontend"])
        t_traffic = time.perf_counter()
        keep_lanes = np.sort(rng_for(seed, 5).choice(
            n_lanes, size=min(n_lanes, conf["check"]["lanes"]),
            replace=False))
        warm = traffic.warm_steps(t_frames)
        t_join = time.perf_counter()
        with cellmod.ServeCell(eng, slots=n_lanes) as serve:
            stream = serve.stream_lanes(
                fcfg, detector.DetectorConfig(), chunk_hops=traffic.k,
                feature_ingest=traffic.feature)
            for lane in range(n_lanes):
                stream.join(lane)
            jax.block_until_ready(stream.state)
            join_s = time.perf_counter() - t_join
            for step in range(warm):
                stream.hop(traffic.chunk(step))
            setup_s = time.perf_counter() - t_start
            log(f"set-up {setup_s:.3f} s: start and chip "
                f"{t_init - t_start:.3f} s, weights and compile_model "
                f"{t_model - t_init:.3f} s, traffic bank "
                f"{t_traffic - t_model:.3f} s, joins of {n_lanes} lanes "
                f"{join_s:.3f} s, {warm} warm-up steps "
                f"{setup_s - (t_join - t_start) - join_s:.3f} s")
            before = counters()
            with profiled(trace) as trace_dir:
                w = _timed_window(stream, traffic, warm,
                                  min(seconds, TRACE_SECONDS) if trace
                                  else seconds, keep_lanes, counter, trace)
            counted = counter_increase(before, counters())
            stats = dev.memory_stats() or {}
            mem_peak = int(stats.get("peak_bytes_in_use", 0))
            host_w = weights.to_host(params)
            del stream
        del eng, params, serve
    finally:
        counter.close()

    lane_hops = w.steps * n_lanes * traffic.k
    lat_ms = 1e3 * np.asarray(w.latencies)
    per_second = np.bincount(np.cumsum(w.latencies).astype(int))
    log(f"steps in each second of the window: {per_second.tolist()}")
    log(f"window {w.seconds:.3f} s: {w.steps} steps of {n_lanes} lanes, "
        f"step p50 {np.median(lat_ms):.4f} ms p95 "
        f"{np.percentile(lat_ms, 95):.4f} ms max {lat_ms.max():.4f} ms; "
        f"chunk preparation {w.prep_seconds:.4f} s "
        f"({100 * w.prep_seconds / w.seconds:.3f}% of the window); "
        f"memory peak {mem_peak} B")

    # -- the check, after the window and with the program's state freed --
    t_ref = time.perf_counter()
    steps = sorted(w.kept)
    pairs = _sample_pairs(seed, list(range(len(keep_lanes))), steps,
                          conf["check"]["pairs"])
    x = _reference_inputs(traffic,
                          conf, [(int(keep_lanes[i]), s) for i, s in pairs],
                          t_frames)
    stated = reference.Numerics.stated(conf["numerics"])
    ref = reference.kwt_logits(host_w, x, model, stated)
    served = np.stack([w.kept[s][i] for i, s in pairs])
    log(f"reference over {len(pairs)} sampled lane-hops in "
        f"{time.perf_counter() - t_ref:.3f} s")
    limits = conf["check"]["limits"]
    readings = logit_checks(served, ref)
    log("readings: " + ", ".join(f"{k} {v!r}" for k, v in readings.items()))
    checks = {name: {"value": v, "limit": limits[name]}
              for name, v in readings.items() if name in limits}
    checks.update(window_compiles={"value": w.compiles, "limit": 0},
                  nonfinite_lane_hops={"value": w.nonfinite, "limit": 0})

    lows = {}
    if control:
        lows = {part: reference.kwt_logits(host_w, x, model,
                                           stated.lowered(part))
                for part in CONTROLS}
    if record is not None:
        record.update(served=served, ref=ref, weights=host_w,
                      pairs=np.array([(keep_lanes[i], s) for i, s in pairs]),
                      **{f"control.{p}": low for p, low in lows.items()})
    red = reduce_trace(trace_dir) if trace else None
    ctx = _reader_context(cell, conf, n_lanes, traffic.k, w, red,
                          dev.device_kind, counted) if trace else None
    result = result_line(
        cell, dev, count, mem_peak, checks, attempted=lane_hops,
        failed=w.nonfinite,
        values=None if trace else {
            "setup_s": setup_s,
            "stream_capacity": lane_hops * HOP_SECONDS / w.seconds,
            "hop_p95_ms": float(np.percentile(lat_ms, 95)),
        },
        red=red, ctx=ctx,
        control={part: logit_checks(low, ref) for part, low in lows.items()}
        if control else None)
    return result, checks


@contextlib.contextmanager
def profiled(on: bool):
    """The profiler around the timed window, when ``on``; yields the
    directory its trace goes to (``None`` when off)."""
    if not on:
        yield None
        return
    import jax
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(trace_dir)
    try:
        yield trace_dir
    finally:
        jax.profiler.stop_trace()


def reduce_trace(trace_dir: str):
    """The traced window's reduction, the program's spans and named scopes
    with it; the trace itself is deleted."""
    red = trace_mod.reduce_dir(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return red


def counters() -> dict:
    """Every counter on the program's telemetry registry, the one a
    ``ServeCell`` writes to, by name and labels (``name`` or
    ``name{key="value",...}``, the labels sorted)."""
    from repro import telemetry
    from repro.telemetry.metrics import Counter
    out = {}
    for m in telemetry.default_registry().metrics():
        if isinstance(m, Counter):
            labels = ",".join(f'{k}="{v}"'
                              for k, v in sorted((m.labels or {}).items()))
            out[f"{m.name}{{{labels}}}" if labels else m.name] = m.value
    return out


def counter_increase(before: dict, after: dict) -> dict:
    """Each counter's increase from ``before`` to ``after``
    (:func:`counters`)."""
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


CONTAINER_OPS = ("while",)   # a loop's op spans the ops of its body


def result_line(cell, dev, count: int, mem_peak: int, checks: dict, *,
                attempted: int, failed: int, values: dict | None = None,
                red=None, ctx: dict | None = None,
                control: dict | None = None) -> dict:
    """The run's result line, on either path.  An untraced run reports
    ``values``, its end-to-end metrics, as far as the cell has them; a
    traced one the per-layer metrics that the cell's readers find in
    ``ctx`` (built on ``red``, the trace's reduction), the device's busy
    seconds and the breakdown.  ``checks`` come last."""
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": int(attempted), "failed": int(failed)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count, "memory_peak_bytes": mem_peak}
    if red is None:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items() if k in units}
    else:
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"], cell.bench)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=red.busy_s, window_s=red.window_s)
    result["device"] = device
    if red is not None:
        ops = [op for op in red.top_ops(len(red.ops))
               if op[0].rsplit("/", 1)[-1] not in CONTAINER_OPS]
        result["breakdown"] = {"device_ops": ops[:10],
                               "idle_gaps": red.idle_by_span(10)}
    if control is not None:
        result["control"] = control
    result["checks"] = checks
    return result


def _reader_context(cell, conf, n_lanes, k, w, red, kind: str,
                    counters: dict) -> dict:
    """What the per-layer readers of a traced stream run read: the trace's
    reduction (``trace``), ``counters`` (the increase of each program
    counter over the window) and under ``work`` the work of a lane-hop
    and of an encoder step, counted from shapes."""
    from yardstick import peaks
    model = conf["model"]
    chip = peaks.peaks(kind)
    steps = w.steps
    return {
        "cell": cell.name, "lanes": n_lanes, "chunk_hops": k,
        "steps": steps, "lane_hops": steps * n_lanes * k,
        "trace": red, "counters": counters,
        "work": {
            "step_flops": work.step_flops(model, k),
            "encoder_flops": work.encoder_flops(model),
            "encoder_min_bytes": work.encoder_min_bytes(
                model, n_lanes, conf["numerics"]["weight_bits"]
                or conf["numerics"]["stored_weight_bits"]),
        },
        "peak_ops_per_s": chip[conf["numerics"]["peak"]],
        "hbm_bytes_per_s": chip["hbm_bytes_per_s"],
    }
