#!/usr/bin/env python3
"""Chip smoke test: drive the KWS serve path once on one TPU through the
entry points a user calls, and check what comes out.

Run from the root of a checkout, in one process, on a machine with one
TPU chip:

    python chip_smoke.py

It refuses to run anywhere else: when JAX's first device is not a TPU it
exits nonzero, names the platform it found, and prints no result.

Phases (one summary line each; a failed check raises and the script
exits nonzero):

  A  offline forward at full width: ``kwt-tiny`` (the paper's whole
     model) at batch 64 through ``runtime.compile_model`` for float,
     lut_float, lut and pallas, and ``kwt-1`` at its published 12 layers
     for float, lut and pallas.  float is checked against the plain
     float32 reference (``models.reference``), lut_float and lut against
     float, and pallas must equal lut bit for bit while running compiled
     Mosaic kernels.
  B  stream cell: ``launch.stream_serve`` for lut and pallas with a few
     hundred lanes and a short on-chip training run; every admitted
     stream must be served.  Then, on a ``StreamLanes`` cell, the served
     logits of each lane must equal ``Engine.forward`` on the window the
     lane assembled.
  C  LM scheduler: ``launch.serve`` on the internlm2-1.8b smoke config
     for float and lut; every request must get the tokens it asked for.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Compiled
programs persist where ``runtime.enable_compile_cache`` says.
"""

from __future__ import annotations

import json
import os
import sys
import time
from importlib import metadata

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
BATCH = 64
STREAM_SLOTS = 256
# |float - lut| logit bound of the parity tests (tests/test_runtime.py)
FLOAT_VS_LUT_TOL = 0.35
# The float plan runs the chip's default one-pass bfloat16 matmuls; the
# reference runs at "highest".  Rounding only the weights and inputs to
# bfloat16 moves KWT-1 logits by 1.1% of their largest magnitude (CPU
# emulation), so 5% of max(1, |ref|) leaves room for the activations.
FLOAT_REF_RTOL = 0.05


def _device():
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    return dev, len(devices)


def _maxdiff(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _logits(eng, x, cfg):
    import jax
    import numpy as np
    out = np.asarray(jax.block_until_ready(eng.forward(x)))
    _check(out.shape == (x.shape[0], cfg.n_classes),
           f"{cfg.name}/{eng.backend_name}: logits shape {out.shape}")
    _check(bool(np.isfinite(out).all()),
           f"{cfg.name}/{eng.backend_name}: non-finite logits")
    return out


def phase_a() -> str:
    """Offline forward per backend at full width, against the reference."""
    import jax
    import numpy as np
    from repro import runtime
    from repro.configs import registry
    from repro.data import pipeline
    from repro.models import kwt, reference

    parts = []
    for name, backends in (("kwt-tiny", ("float", "lut_float", "lut",
                                         "pallas")),
                           ("kwt-1", ("float", "lut", "pallas"))):
        cfg = registry.get(name).config
        params = kwt.init_params(cfg, jax.random.PRNGKey(SEED))
        x = pipeline.gsc_eval_set(SEED, n=BATCH, input_dim=cfg.input_dim,
                                  n_classes=cfg.n_classes,
                                  batch=BATCH)[0]["mfcc"]
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(
                lambda p, m: reference.kwt_forward(p, m, cfg))(params, x))
        out, engines = {}, {}
        for b in backends:
            engines[b] = runtime.compile_model(cfg, params, backend=b)
            out[b] = _logits(engines[b], x, cfg)

        d_ref = _maxdiff(out["float"], ref)
        tol = FLOAT_REF_RTOL * max(1.0, float(np.abs(ref).max()))
        _check(d_ref <= tol, f"{name}: float vs reference {d_ref} > {tol}")
        line = f"{name} float-ref={d_ref:.3g} (tol {tol:.3g})"
        for b in ("lut_float", "lut"):
            if b in out:
                d = _maxdiff(out[b], out["float"])
                if name == "kwt-tiny":
                    _check(d < FLOAT_VS_LUT_TOL,
                           f"{name}: {b} vs float {d} >= {FLOAT_VS_LUT_TOL}")
                line += f" {b}-float={d:.3g}"

        eng = engines["pallas"]
        _check(eng.interpret is False,
               f"{name}: pallas plan runs in interpret mode")
        hlo = jax.jit(lambda p, m: kwt.forward(p, m, eng.exec_cfg)) \
            .lower(eng.params, x).compile().as_text()
        n_kernels = hlo.count("tpu_custom_call")
        _check(n_kernels > 0, f"{name}: no tpu_custom_call in pallas HLO")
        d_pl = _maxdiff(out["pallas"], out["lut"])
        _check(d_pl == 0.0, f"{name}: pallas differs from lut by {d_pl}")
        parts.append(f"{line} pallas-lut={d_pl:.3g} "
                     f"(mosaic, {n_kernels} custom calls)")
    return "; ".join(parts)


def phase_b() -> str:
    """The stream cell through its launcher, then streaming-vs-offline
    bit identity on a StreamLanes cell."""
    import jax
    import numpy as np
    from repro import cell as cellmod
    from repro import runtime
    from repro.configs import registry
    from repro.launch import stream_serve
    from repro.models import kwt
    from repro.stream import detector as det
    from repro.stream import engine as stream_engine
    from repro.stream import features

    parts = []
    for b in ("lut", "pallas"):
        s = stream_serve.main([
            "--backend", b, "--slots", str(STREAM_SLOTS),
            "--streams", str(2 * STREAM_SLOTS), "--hops", "40",
            "--train-steps", "10", "--seed", str(SEED)])
        _check(s["shed"] == 0 and s["served"] == s["streams"],
               f"stream {b}: served {s['served']} of {s['streams']} "
               f"admitted, shed {s['shed']}")
        _check(s["ingested_hops"] == s["offered_hops"],
               f"stream {b}: ingested {s['ingested_hops']} of "
               f"{s['offered_hops']} offered hops")
        parts.append(f"{b} served {s['served']}/{s['streams']} streams "
                     f"({s['ingested_hops']} hops, p50 hop "
                     f"{s['p50_ms']:.3g} ms)")

    cfg = registry.get("kwt-tiny").config
    fcfg, dcfg = features.FrontendConfig(), det.DetectorConfig()
    params = kwt.init_params(cfg, jax.random.PRNGKey(SEED))
    lanes_n, hops = 8, stream_engine.window_frames(cfg) + 4
    audio = 0.1 * np.asarray(jax.random.normal(
        jax.random.PRNGKey(SEED + 1), (lanes_n, hops * fcfg.hop_len)),
        np.float32)
    for b in ("lut", "pallas"):
        eng = runtime.compile_model(cfg, params, backend=b)
        with cellmod.ServeCell(eng, slots=lanes_n) as cell:
            lanes = cell.stream_lanes(fcfg, dcfg, keep_features=True)
            for lane in range(lanes_n):
                lanes.join(lane)
            for h in range(hops):
                ev = lanes.hop(audio[:, h * fcfg.hop_len:
                                     (h + 1) * fcfg.hop_len])
            _check(bool(np.asarray(stream_engine.warm(lanes.state)).all()),
                   f"stream {b}: lanes not warm after {hops} hops")
            off = _logits(eng, stream_engine.window_mfcc(lanes.state), cfg)
        d = _maxdiff(ev["logits"], off)
        _check(d == 0.0, f"stream {b}: streaming logits differ from "
                         f"offline forward by {d}")
        parts.append(f"{b} stream==offline on {lanes_n} lanes")
    return "; ".join(parts)


def phase_c() -> str:
    """LM continuous batching through the serve launcher."""
    from repro.launch import serve

    parts = []
    for b in ("float", "lut"):
        requests, out = serve.main([
            "--arch", "internlm2-1.8b", "--smoke", "--requests", "6",
            "--slots", "4", "--max-len", "32", "--backend", b,
            "--seed", str(SEED)])
        for r in requests:
            got = len(out.get(r["id"], ()))
            _check(got == r["gen"], f"lm {b}: request {r['id']} got {got} "
                                    f"of {r['gen']} tokens")
        parts.append(f"{b} {len(requests)} requests, "
                     f"{sum(r['gen'] for r in requests)} tokens")
    return "; ".join(parts)


def main() -> int:
    t_start = time.perf_counter()
    dev, count = _device()
    import jax
    print(f"device: {dev.device_kind} x{count} ({dev.platform})", flush=True)
    print(f"versions: jax {jax.__version__}, jaxlib "
          f"{metadata.version('jaxlib')}, libtpu "
          f"{metadata.version('libtpu')}", flush=True)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import runtime
    print(f"compile cache: {runtime.enable_compile_cache()}", flush=True)

    for tag, phase in (("A", phase_a), ("B", phase_b), ("C", phase_c)):
        t0 = time.perf_counter()
        summary = phase()
        print(f"phase {tag} ok ({time.perf_counter() - t0:.1f} s): {summary}",
              flush=True)
    print(f"all phases ok in {time.perf_counter() - t_start:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
