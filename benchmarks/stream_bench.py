"""Serving-cell benchmark: per-hop latency, real-time factor, LM tokens/s.

Every row is produced through :class:`repro.cell.ServeCell` — the same
lane pool, fused engine+detector hop, and metrics ledger the serve
launchers run — not a bench-only loop.  Two ingest modes, reported
side by side:

* ``audio``: lanes ingest raw waveform chunks and the cell runs the
  full MFCC frontend per hop.  This includes the FFT, which is the
  dominant per-hop cost at wide batches.
* ``feature``: lanes ingest pre-featurised MFCC frames
  (``stream.engine.stream_step_frames``) — the paper's deployment
  split, where the MCU next to the microphone owns featurisation and
  the cell serves the encoder+detector.  Frames from
  ``features.frontend_push`` are bit-identical to the audio path
  (tests/test_cell.py), so this row measures the same model, minus the
  edge-resident stage.

RTF (real-time factor) = wall time per hop / audio time per hop: every
stream delivers ``chunk_hops * hop_len`` samples per step, and the
whole packed batch must be processed inside that budget regardless of
width — RTF < 1 means the cell keeps up with all N streams on this
host.  Wide-stream rows use ``chunk_hops`` > 1 (the admission
controller's degrade mode) to amortise the per-step encoder pass.

The ``lm`` section drives :class:`repro.cell.scheduler.LMScheduler`
(continuous batching) at mixed prefill/decode load and reports
decoded tokens/s.

Usage:  PYTHONPATH=src python -m benchmarks.stream_bench \
            [--streams 1 64 1024 4096] [--hops 50] [--out BENCH_stream.json]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro import cell as cellmod
from repro import perf
from repro import runtime
from repro import telemetry
from repro.configs import registry
from repro.launch import steps
from repro.models import kwt
from repro.stream import detector as det
from repro.stream import engine
from repro.stream import features


def bench_one(eng, fcfg, dcfg, n_streams: int, hops: int, chunk_hops: int,
              ingest: str, seed: int = 0) -> dict:
    """Time ``hops`` cell hops at ``n_streams`` fully occupied lanes."""
    k = chunk_hops
    cfg = eng.exec_cfg
    rng = np.random.RandomState(seed)
    cell = cellmod.ServeCell(eng, slots=n_streams,
                             registry=telemetry.Registry())
    with cell:
        lanes = cell.stream_lanes(fcfg, dcfg, chunk_hops=k,
                                  feature_ingest=(ingest == "feature"))
        for lane in range(n_streams):
            lanes.join(lane)
        if ingest == "feature":
            chunk = 0.1 * rng.randn(n_streams, k,
                                    cfg.input_dim[0]).astype(np.float32)
        else:
            chunk = 0.1 * rng.randn(n_streams,
                                    k * fcfg.hop_len).astype(np.float32)
        chunk = jax.device_put(chunk)

        # warm-up (discarded): compile + fill the receptive field
        warm_hops = engine.window_frames(cfg) // k + 2
        for _ in range(warm_hops):
            lanes.hop(chunk)

        # per-hop samples; lanes.hop syncs on the detector events each
        # call — the real serving cadence (events are consumed on host
        # every hop), so these samples ARE the serve-path latency.
        samples = []
        t0 = time.perf_counter()
        for _ in range(hops):
            t1 = time.perf_counter()
            lanes.hop(chunk)
            samples.append((time.perf_counter() - t1) * 1e3)
        dt = time.perf_counter() - t0
        assert int(cell.metrics.hops.value) == (warm_hops + hops) * k \
            * n_streams and cell.metrics.dropped_hops.value == 0

    per_step_ms = dt / hops * 1e3
    audio_ms = k * fcfg.hop_len / fcfg.sample_rate * 1e3
    rtf = per_step_ms / audio_ms
    return {"streams": n_streams, "ingest": ingest, "chunk_hops": k,
            "warmup_hops": warm_hops,
            "per_step_ms": round(per_step_ms, 4),
            **telemetry.latency_summary(samples, unit="ms"),
            "rtf": round(rtf, 5),
            "aggregate_realtime_x": round(n_streams / rtf, 1)}


def bench_lm(backend: str, slots: int, requests: int, max_len: int,
             seed: int = 0) -> dict:
    """Continuous-batching throughput: tokens/s at mixed prefill/decode
    load (new requests prefill into free lanes while residents decode)."""
    cfg = registry.get("internlm2-1.8b").smoke
    params = steps.model_module(cfg).init_params(cfg,
                                                 jax.random.PRNGKey(seed))
    eng = runtime.compile_model(cfg, params, backend=backend)
    rng = np.random.RandomState(seed)
    reqs = [(i, rng.randint(0, cfg.vocab_size,
                            size=rng.randint(4, max_len // 4)),
             int(rng.randint(4, max_len // 2))) for i in range(requests)]
    cell = cellmod.ServeCell(eng, slots=slots, registry=telemetry.Registry())
    with cell:
        sched = cell.lm_scheduler(max_len=max_len)
        for rid, prompt, gen in reqs:
            sched.submit(rid, prompt, gen)
        sched.run()          # warm-up: compile prefill/decode variants
        for rid, prompt, gen in reqs:
            sched.submit(rid, prompt, gen)
        t0 = time.perf_counter()
        out = sched.run()
        dt = time.perf_counter() - t0
    decoded = sum(len(v) for v in out.values())
    m = cell.metrics
    return {"arch": "internlm2-1.8b", "mode": backend, "slots": slots,
            "requests": requests, "max_len": max_len,
            "decode_tokens": decoded,
            "prefill_tokens": int(m.prefill_tokens.value) // 2,
            "wall_s": round(dt, 4),
            "tokens_per_s": round(decoded / dt, 2),
            "ms_per_token": round(1e3 * dt / max(decoded, 1), 4),
            "packed_rom_bytes": eng.rom_bytes}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="kwt-tiny")
    ap.add_argument("--streams", type=int, nargs="+",
                    default=[1, 64, 1024, 4096])
    ap.add_argument("--hops", type=int, default=50)
    ap.add_argument("--chunk-hops", type=int, default=1,
                    help="hops per step for the audio-ingest rows")
    ap.add_argument("--wide-chunk-hops", type=int, default=None,
                    help="hops per step for wide-batch rows (default: the "
                         "full window, the deepest degrade the ring admits)")
    ap.add_argument("--wide-streams", type=int, default=4096,
                    help="rows at/above this width also run feature ingest "
                         "and the widened chunk")
    ap.add_argument("--backends", nargs="+", default=["float", "lut"],
                    help="runtime backends to sweep (pallas interpret is "
                         "slow on CPU; add it explicitly when wanted)")
    ap.add_argument("--lm-slots", type=int, default=4)
    ap.add_argument("--lm-requests", type=int, default=16)
    ap.add_argument("--lm-max-len", type=int, default=64)
    ap.add_argument("--no-lm", action="store_true")
    ap.add_argument("--out", default="BENCH_stream.json")
    ap.add_argument("--history", default=None,
                    help="append sweep rows to this bench ledger "
                         "(BENCH_history.jsonl) for repro.perf regress")
    args = ap.parse_args(argv)
    runtime.enable_compile_cache()

    base = registry.get(args.arch).smoke
    fcfg = features.FrontendConfig()
    dcfg = det.DetectorConfig()
    params = kwt.init_params(base, jax.random.PRNGKey(0))
    wide_k = args.wide_chunk_hops if args.wide_chunk_hops is not None \
        else engine.window_frames(base)
    machine = perf.host_machine()
    prov = perf.provenance(machine)

    results = []
    print("mode,ingest,streams,chunk_hops,per_step_ms,p50_ms,p95_ms,rtf,"
          "aggregate_realtime_x,roof_pct,bound")
    for b in args.backends:
        eng = runtime.compile_model(base, params, backend=b)
        for n in args.streams:
            rows = [("audio", args.chunk_hops)]
            if n >= args.wide_streams:
                # wide batch: degraded chunk (audio) + edge-featurised
                # ingest — both honest cell modes, reported side by side
                rows += [("audio", wide_k), ("feature", wide_k)]
            for ingest, k in rows:
                r = {"mode": b,
                     **bench_one(eng, fcfg, dcfg, n, args.hops, k, ingest)}
                # static cost of exactly this hop program, roofed
                # against the calibrated host
                cost = perf.stream_hop_cost(
                    eng, fcfg, batch=n, chunk_hops=k,
                    feature_ingest=(ingest == "feature"))
                r.update(perf.roofline_terms(cost.flops, cost.bytes,
                                             r["per_step_ms"] / 1e3,
                                             machine))
                r["packed_rom_bytes"] = eng.rom_bytes
                results.append(r)
                print(f"{b},{ingest},{n},{k},{r['per_step_ms']},"
                      f"{r['p50_ms']},{r['p95_ms']},{r['rtf']},"
                      f"{r['aggregate_realtime_x']},"
                      f"{r['achieved_pct_of_roof']},{r['bound']}")

    report = {"arch": args.arch,
              "host": {"cpus": os.cpu_count(),
                       "backend": jax.default_backend()},
              "provenance": prov, "machine": machine.to_dict(),
              "frontend": {"sample_rate": fcfg.sample_rate,
                           "frame_len": fcfg.frame_len,
                           "hop_len": fcfg.hop_len,
                           "window_frames": engine.window_frames(base)},
              "results": results}
    if not args.no_lm:
        report["lm"] = [bench_lm(b, args.lm_slots, args.lm_requests,
                                 args.lm_max_len)
                        for b in args.backends]
        for r in report["lm"]:
            print(f"lm,{r['mode']},slots={r['slots']},"
                  f"req={r['requests']},tok/s={r['tokens_per_s']}")
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    if args.history:
        entries = [perf.entry(
            args.arch, f"{r['mode']}/{r['ingest']}@k{r['chunk_hops']}",
            r["streams"], r["per_step_ms"], "ms_per_hop",
            rom_bytes=r["packed_rom_bytes"],
            extra={"rtf": r["rtf"],
                   "achieved_pct_of_roof": r["achieved_pct_of_roof"],
                   "bound": r["bound"]},
            prov=prov) for r in results]
        entries += [perf.entry(
            r["arch"], f"{r['mode']}/lm", r["slots"], r["ms_per_token"],
            "ms_per_token", rom_bytes=r["packed_rom_bytes"],
            extra={"tokens_per_s": r["tokens_per_s"]}, prov=prov)
            for r in report.get("lm", [])]
        print(f"appended {perf.append(args.history, entries)} entries "
              f"to {args.history}")

    worst_small = max((r["rtf"] for r in results if r["streams"] <= 64),
                      default=None)
    best_wide = min((r["rtf"] for r in results
                     if r["streams"] >= args.wide_streams), default=None)
    ok = True
    if worst_small is not None:
        ok &= worst_small < 1.0
        print(f"RTF @ <=64 streams (audio): {worst_small} "
              f"({'OK' if worst_small < 1.0 else 'OVER BUDGET'})")
    if best_wide is not None:
        ok &= best_wide < 1.0
        print(f"best RTF @ >={args.wide_streams} streams: {best_wide} "
              f"({'OK' if best_wide < 1.0 else 'OVER BUDGET'})")
    return 0 if ok else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
