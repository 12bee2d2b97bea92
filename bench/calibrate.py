#!/usr/bin/env python3
"""Readings that a cell's ``correct`` limits are set from, in one process
on the chip:

    python bench/calibrate.py --workload <cell> --seeds <n1> <n2> ... \\
        [--seconds 2] [--program-bits 4] [--program-override KEY=VALUE]
        [--out <dir>]

For each seed it runs the cell at its own lane (or slot) count with a
short window, and prints the compared numbers of the program and of the
controls: the reference one precision step below the stated numerics in
the integer grids or linear operands, the attention operands or the
float intermediates (on the LM path: its weights), each in the program's
place on the same inputs.  With ``--program-bits 4`` the program serves
its own 4-bit weights, and with ``--program-override`` (LM path) a key of
its configuration set apart from the file, such as a wrong
``rope_theta``: its readings are then another control's.  With ``--out``
the numbers compared go to ``<dir>/<cell>_<seed>.npz``.  The last line
gives the largest program reading (the lower one) and the smallest of
each control (the upper).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--program-bits", type=int, default=None,
                    help="serve the program's own weights at this width "
                         "(its lower-precision control) instead")
    ap.add_argument("--program-override", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="LM path: set a key of the program's configuration "
                         "apart from the file (a planted fault)")
    ap.add_argument("--out", type=Path, default=None,
                    help="directory for the logits compared")
    args = ap.parse_args(argv)

    overrides = {k: float(v) for k, v in
                 (kv.split("=", 1) for kv in args.program_override)}
    import jax
    from yardstick import harness
    program, control = {}, {}
    for seed in args.seeds:
        record = {}
        result, checks = harness.run(args.workload, seed, args.seconds,
                                     False, control=True,
                                     program_bits=args.program_bits,
                                     program_overrides=overrides or None,
                                     record=record,
                                     log=lambda s: print(f"  {s}",
                                                         flush=True))
        if args.out is not None:
            args.out.mkdir(parents=True, exist_ok=True)
            leaves = jax.tree_util.tree_flatten_with_path(
                record.pop("weights", {}))[0]
            np.savez(args.out / f"{args.workload}_{seed}.npz", **record,
                     **{"w" + jax.tree_util.keystr(path): np.float32(leaf)
                        for path, leaf in leaves})
        # drop this seed's compiled programs: each holds device memory
        # while loaded, and the next seed's engine compiles its own
        jax.clear_caches()
        row = {"workload": args.workload, "seed": seed,
               "correct": result["correct"],
               "program": {k: c["value"] for k, c in checks.items()},
               "control": result["control"]}
        print(json.dumps(row), flush=True)
        for k, v in row["program"].items():
            program[k] = max(program.get(k, v), v)
        for part, nums in row["control"].items():
            low = control.setdefault(part, dict(nums))
            for k, v in nums.items():
                low[k] = min(low[k], v)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "lower": program, "upper": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
