"""Benchmark harness: one function per paper table (benchmarks.paper_tables)
plus kernel micro-benchmarks.  Prints ``name,us_per_call,derived`` CSV.

``--backend-sweep`` times one KWT-Tiny forward per runtime backend
(float / lut_float / lut / pallas-interpret) through the same
``runtime.compile_model`` Engine the launchers serve with, and emits
``BENCH_runtime.json`` — the start of the per-backend latency trajectory.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--backend-sweep]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax


def bench_kernels():
    """Pallas kernels (interpret mode off the chip): per-call wall time vs ref."""
    from repro.kernels import ops, ref
    from repro.runtime import plan_interpret

    interpret = plan_interpret()
    mode = "interpret_mode" if interpret else "mosaic"

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 512))
    for name, fn in [
        ("kernel_lut_gelu", lambda: ops.lut_gelu(x, interpret=interpret)),
        ("ref_lut_gelu", lambda: ref.lut_gelu(x)),
        ("kernel_lut_softmax",
         lambda: ops.lut_softmax(x, interpret=interpret)),
        ("ref_lut_softmax", lambda: ref.lut_softmax(x)),
    ]:
        fn()
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn()
        jax.block_until_ready(out)
        print(f"{name},{(time.perf_counter()-t0)/5*1e6:.1f},{mode}")
    q = jax.random.normal(key, (1, 4, 128, 64))
    k = jax.random.normal(key, (1, 2, 128, 64))
    t0 = time.perf_counter()
    out = ops.lut_attention(q, k, k, interpret=interpret)
    jax.block_until_ready(out)
    print(f"kernel_lut_attention,{(time.perf_counter()-t0)*1e6:.1f},"
          f"{mode}_single_call")


def bench_backend_sweep(out_path: str = "BENCH_runtime.json",
                        batch: int = 64, reps: int = 20,
                        warmup: int = 3,
                        history: str | None = None) -> dict:
    """Per-backend forward latency of the Engine the launchers actually
    serve (runtime.compile_model on KWT-Tiny), emitted as JSON.

    Timing protocol: ``warmup`` calls are discarded (compile + cache
    effects), then ``reps`` calls are timed per call with a sync each —
    those samples feed the telemetry latency schema (``mean_us``/
    ``p50_us``/``p95_us``/``p99_us``, the same field names the serve
    metrics export; ``mean_us`` is the trajectory + ledger figure).

    A final traced pass (``telemetry.tracing``) attributes each forward
    to its stage spans: ``unpack_us`` (jitted QTensor dequant — 0 for
    integer-executing plans, which have no unpack stage at all) and
    ``encode_us`` (the model executable), plus ``span_coverage_pct``
    (named children / forward wall time) and
    ``telemetry_overhead_pct`` (traced vs untraced per-call mean).

    ``packed_rom_bytes`` is the TRUE packed integer weight image
    (``Engine.rom_bytes``: int8, or nibble-packed int4 for the extra
    ``lut@int4`` row); ``lut_bytes`` the 2.69 kB LUT bank.

    Each row also carries the static-analysis verdict for its plan:
    ``float_leak_count`` (residency pass: int->float casts in the unpack
    stage — the number that must reach zero for full-integer execution)
    and ``ram_budget_bytes`` (budget pass: ROM + LUT + peak activation
    live-set, the figure gated against the paper's 64 kB target).

    Cost accounting (repro.perf): every row carries the static cost
    model's ``flops`` / ``bytes_moved`` / ``arithmetic_intensity`` for
    its plan, the achieved fraction of the *calibrated host roofline*
    at that intensity (``achieved_pct_of_roof`` + compute/memory
    ``bound`` verdict — the ROADMAP's achieved-vs-peak column), and
    ``est_mcu_cycles``: the per-sample plan priced on the paper's RV32
    MCU model, the unit of the paper's 26M → 5.5M ledger.  With
    ``history`` set, every row is also appended to the bench ledger
    (``repro.perf.ledger``) for the CI regression gate, plus a derived
    ``lut_over_float`` ratio entry (lut mean_us / float mean_us) so the
    gate guards the int-exec plan staying FASTER than float."""
    import numpy as np

    from repro import analysis, perf, runtime, telemetry
    from repro.configs import registry
    from repro.models import kwt

    cfg = registry.get("kwt-tiny").config
    params = kwt.init_params(cfg, jax.random.PRNGKey(0))
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(1),
                                (batch, *cfg.input_dim))
    machine = perf.host_machine()
    prov = perf.provenance(machine)
    plans = [(name, None) for name in runtime.available_backends()]
    plans.append(("lut", runtime.QuantRecipe.from_config(
        cfg, bits=4).calibrated(params)))          # the int4 storage row
    # Compile + warm every plan FIRST, then round-robin the timed reps
    # across all of them.  On a shared CI core, sequential per-backend
    # windows alias scheduler noise onto whichever backend ran during a
    # burst — the gated lut/float ratio flipped sign run-to-run.
    # Interleaving makes each backend's samples face the same noise
    # process, so cross-backend ratios are paired statistics.
    engines = []
    for name, recipe in plans:
        eng = runtime.compile_model(cfg, params, backend=name, recipe=recipe)
        for _ in range(max(warmup, 1)):              # compile + warm, discard
            jax.block_until_ready(eng.forward(x))
        engines.append((name, recipe, eng, []))
    for _ in range(reps):
        for _, _, eng, samples in engines:           # per-call, synced
            t1 = time.perf_counter()
            jax.block_until_ready(eng.forward(x))
            samples.append((time.perf_counter() - t1) * 1e6)
    results = []
    for name, recipe, eng, samples in engines:
        lat = telemetry.latency_summary(samples, unit="us")
        us = lat["mean_us"]
        with telemetry.tracing() as tr:              # stage attribution
            for _ in range(reps):
                eng.forward(x)
        ups = tr.durations_us("unpack")              # absent for int-exec
        unpack_us = float(np.mean(ups)) if len(ups) else 0.0
        encode_us = float(np.mean(tr.durations_us("encode")))
        traced_us = float(np.median(tr.durations_us("forward")))
        coverage = telemetry.span_coverage(tr, "forward")
        # median-vs-median: per-call means on a shared CPU are dominated
        # by scheduler noise, which would read as fake "overhead"
        overhead = 100.0 * (traced_us - lat["p50_us"]) / lat["p50_us"]
        bits = eng.recipe.bits if eng.recipe is not None else None
        label = name if recipe is None else f"{name}@int{bits}"
        rep = analysis.check_engine(eng, passes=("residency", "budget"))
        leaks = rep.result("residency").metrics["float_leak_count"]
        ram = rep.result("budget").metrics["total_bytes"]
        cost = perf.engine_cost(eng, batch=batch)
        cost1 = perf.engine_cost(eng, batch=1)     # per-sample, MCU units
        row = {"backend": label,
               **lat,
               **perf.roofline_terms(cost.flops, cost.bytes, us / 1e6,
                                     machine),
               "est_mcu_cycles": round(perf.PAPER_MCU.cycles(cost1.flops,
                                                             cost1.bytes)),
               "unpack_us": round(unpack_us, 1),
               "encode_us": round(encode_us, 1),
               "span_coverage_pct": round(100.0 * coverage, 1),
               "telemetry_overhead_pct": round(overhead, 2),
               "warmup": warmup,
               "batch": batch, "interpret": eng.interpret,
               "packed_rom_bytes": eng.rom_bytes,
               "lut_bytes": eng.lut_bytes,
               "param_bytes": eng.param_bytes,
               "int_resident": eng.int_resident,
               "int_exec": eng.int_exec, "bits": bits,
               "float_leak_count": leaks,
               "ram_budget_bytes": ram,
               "analysis_ok": rep.ok}
        results.append(row)
        print(f"backend_{label},{us:.1f},p50={lat['p50_us']}us;"
              f"p95={lat['p95_us']}us;unpack={unpack_us:.1f}us;"
              f"encode={encode_us:.1f}us;rom={eng.rom_bytes}B;"
              f"lut={eng.lut_bytes}B;params={eng.param_bytes}B;"
              f"leaks={leaks};ram={ram}B;roof={row['achieved_pct_of_roof']}"
              f"%({row['bound']});interpret={eng.interpret}")
    report = {"arch": "kwt-tiny", "batch": batch, "reps": reps,
              "warmup": warmup, "device": jax.default_backend(),
              "provenance": prov, "machine": machine.to_dict(),
              "results": results}
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out_path}", file=sys.stderr)
    if history:
        entries = [
            perf.entry("kwt-tiny", r["backend"], batch,
                       r["mean_us"], "mean_us",
                       rom_bytes=r["packed_rom_bytes"],
                       extra={"achieved_pct_of_roof":
                              r["achieved_pct_of_roof"],
                              "achieved_pct_of_peak":
                              r["achieved_pct_of_peak"],
                              "bound": r["bound"],
                              "est_mcu_cycles": r["est_mcu_cycles"]},
                       prov=prov)
            for r in results]
        by_backend = {r["backend"]: r for r in results}
        if "float" in by_backend and "lut" in by_backend:
            # the int-exec acceptance as a guarded ledger figure: lut
            # beating float means ratio < 1, and `perf regress` flags
            # any >15% growth — the unpack-tax win cannot silently rot
            ratio = by_backend["lut"]["mean_us"] / \
                by_backend["float"]["mean_us"]
            entries.append(perf.entry(
                "kwt-tiny", "lut_over_float", batch, round(ratio, 4),
                "ratio_mean_us", rom_bytes=0, prov=prov))
        n = perf.append(history, entries)
        print(f"appended {n} entries to {history}", file=sys.stderr)
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the trained-model tables (fast CI mode)")
    ap.add_argument("--backend-sweep", action="store_true",
                    help="per-backend Engine forward latency -> "
                         "BENCH_runtime.json (skips the paper tables)")
    ap.add_argument("--out", default="BENCH_runtime.json")
    ap.add_argument("--batch", type=int, default=64,
                    help="sweep batch size (CI smoke uses a small one)")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--history", default=None,
                    help="append sweep rows to this bench ledger "
                         "(BENCH_history.jsonl) for repro.perf regress")
    args = ap.parse_args()
    from repro.runtime import enable_compile_cache
    enable_compile_cache()

    if args.backend_sweep:
        print("name,us_per_call,derived")
        bench_backend_sweep(args.out, batch=args.batch, reps=args.reps,
                            history=args.history)
        return

    from benchmarks import paper_tables as pt

    print("name,us_per_call,derived")
    bench_kernels()
    pt.bench_custom_ops()       # Table VII
    pt.bench_lut_cost()         # Table VIII analogue
    pt.bench_op_profile()       # Figs 3-5
    pt.bench_gelu_approx()      # Fig 7
    if not args.quick:
        fam = pt.bench_model_family()    # Tables I/III/IV (trains KWT-Tiny)
        trained = fam.get("trained")
        pt.bench_scale_sweep(trained)    # Table V
        pt.bench_inference_profile(trained)  # Table IX
    print("benchmarks complete.", file=sys.stderr)


if __name__ == "__main__":
    main()
