"""Batched LM serving launcher: a thin CLI over ``repro.cell``.

Continuous batching proper (``cell.scheduler.LMScheduler``): a fixed
pool of batch slots where new requests prefill into free lanes WHILE
resident lanes keep decoding — per-lane decode depths, per-slot
EOS/evict, no drain barrier.  (The previous slot loop re-initialised the
whole decode state on every refill, wiping resident lanes' KV caches
mid-request; the scheduler's fresh-prefill + per-lane merge is the fix,
and tests/test_cell.py pins the resident-preservation property.)

Execution policy is one flag: ``--backend float|lut_float|lut|pallas``
resolves through ``runtime.compile_model`` to an Engine that owns the
paper's pipeline end to end (power-of-2 PTQ weights + LUT softmax/GELU
for the quantising backends, Pallas kernels for ``pallas``), mirroring
the KWT-Tiny-Q (+Hardware) staircase at LM scale.

Usage (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --requests 8 --max-len 64 [--backend lut]
"""

from __future__ import annotations

import time

import argparse

import jax
import numpy as np

from repro import cell as cellmod
from repro import runtime
from repro import telemetry
from repro.configs import registry
from repro.launch import serve_common
from repro.launch import steps


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--backend", default="float",
                    choices=runtime.available_backends(),
                    help="execution backend (runtime.compile_model); "
                         "the former --quantize flag is --backend lut_float")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="evict a lane early when it emits this token")
    ap.add_argument("--seed", type=int, default=0)
    serve_common.add_telemetry_args(ap)
    args = ap.parse_args(argv)
    runtime.enable_compile_cache()

    entry = registry.get(args.arch)
    cfg = entry.smoke if args.smoke else entry.config
    mod = steps.model_module(cfg)
    assert cfg.family != "encdec", "use whisper_serve example for enc-dec"

    rng = np.random.RandomState(args.seed)
    requests = [{"id": i,
                 "prompt": rng.randint(0, cfg.vocab_size,
                                       size=rng.randint(4,
                                                        args.max_len // 4)),
                 "gen": int(rng.randint(4, args.max_len // 2))}
                for i in range(args.requests)]

    with serve_common.session(args.telemetry_out) as (tracer, met):
        params = mod.init_params(cfg, jax.random.PRNGKey(args.seed))
        eng = runtime.compile_model(cfg, params, backend=args.backend)
        telemetry.log("engine", plan=eng.describe(),
                      device=jax.devices()[0].device_kind)
        cell = cellmod.ServeCell(eng, slots=args.slots, registry=met)
        with cell:
            sched = cell.lm_scheduler(max_len=args.max_len,
                                      eos_id=args.eos_id)
            for r in requests:
                sched.submit(r["id"], r["prompt"], r["gen"])
            t0 = time.time()
            out = sched.run()
        dt = time.time() - t0
        decoded = sum(len(v) for v in out.values())
        telemetry.log("serve_done", requests=args.requests, tokens=decoded,
                      wall_s=dt, tok_s=decoded / dt,
                      backend=eng.backend_name,
                      **met.histogram("cell_decode_latency_ms").summary())
    return requests, out


if __name__ == "__main__":
    main()
