"""Roofline report for Engine plans: cost table + achieved-vs-peak CSV.

A thin CLI over :mod:`repro.perf` — calibrate the host, price each
backend's compiled plan with the static cost model, and print the
paper-style (stage, op) table plus one achieved-vs-peak row per
backend.  The sweep drivers (``benchmarks/run.py --backend-sweep``,
``benchmarks/stream_bench.py``) embed the same columns in their JSON
rows; this command is the standalone/inspection view.

  PYTHONPATH=src python -m benchmarks.roofline [--arch kwt-tiny]
      [--backends float lut pallas] [--batch 64] [--mcu] [--smoke]

``--mcu`` prices on the paper's RV32 MCU model (cycles, the 26M → 5.5M
unit) instead of the measured host roofline.
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    import jax

    from repro import perf, runtime
    from repro.configs import registry
    from repro.launch import steps

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="kwt-tiny")
    ap.add_argument("--backends", nargs="+",
                    default=["float", "lut_float", "lut", "pallas"])
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's smoke config")
    ap.add_argument("--mcu", action="store_true",
                    help="price on the paper's RV32 MCU model")
    args = ap.parse_args(argv)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()

    cfg = registry.get(args.arch).smoke if args.smoke \
        else registry.get(args.arch).config
    params = steps.model_module(cfg).init_params(cfg, jax.random.PRNGKey(0))
    machine = perf.PAPER_MCU if args.mcu else perf.host_machine()
    print(f"machine: {machine.id} (ridge {machine.ridge:.2f} flops/byte)\n")

    summary = ["backend,flops,bytes_moved,arithmetic_intensity,bound,"
               "roof_time_us,est_cycles"]
    for backend in args.backends:
        eng = runtime.compile_model(cfg, params, backend=backend)
        rep = perf.engine_cost(eng, batch=args.batch)
        print(f"## {args.arch} · backend={backend} · batch={args.batch}")
        print(rep.table(machine))
        print()
        summary.append(
            f"{backend},{rep.flops:.0f},{rep.bytes:.0f},"
            f"{rep.intensity:.4f},{machine.verdict(rep.intensity)},"
            f"{machine.time_s(rep.flops, rep.bytes) * 1e6:.1f},"
            f"{machine.cycles(rep.flops, rep.bytes):.0f}")
    print("\n".join(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
