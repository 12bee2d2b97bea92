#!/usr/bin/env python3
"""Lane (or slot) sweep of one cell, in one process on the chip:

    python bench/sweep.py --workload <cell> --lanes 256 512 ... \\
        --seconds 3 --seed <n> [--limit-ms 10]

Runs the cell's closed loop at each lane count and prints one JSON line
each, then the largest lane count whose ``hop_p95_ms`` stays within the
hop period (``--limit-ms``): the most streams one chip serves in real
time.  On the LM path the counts are decode slots, and the last line
gives the most slots that ran without running out of device memory.
The mix file's ``lanes`` or ``slots`` is then set to that count by hand.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--lanes", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--limit-ms", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax
    from yardstick import harness
    best, fits = None, None
    for n in args.lanes:
        try:
            result, _ = harness.run(args.workload, args.seed, args.seconds,
                                    False, lanes=n,
                                    log=lambda s: print(f"  {s}", flush=True))
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(json.dumps({"workload": args.workload, "lanes": n,
                              "out_of_memory": str(e)[:300]}), flush=True)
            break
        fits = n
        m = {k: v["value"] for k, v in result["metrics"].items()}
        print(json.dumps({"workload": args.workload, "lanes": n,
                          "correct": result["correct"], **m,
                          "memory_peak_bytes":
                          result["device"]["memory_peak_bytes"],
                          "checks": result["checks"]}), flush=True)
        if m.get("hop_p95_ms", float("inf")) <= args.limit_ms:
            best = n
    print(json.dumps({"workload": args.workload, "real_time_lanes": best,
                      "limit_ms": args.limit_ms, "most_that_ran": fits}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
