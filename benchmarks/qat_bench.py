"""PTQ vs QAT vs QAT+KD at matched ROM bytes -> BENCH_qat.json.

The accuracy half of the paper's pipeline, measured end to end through
the SAME deployment artifact for every variant: each row is accuracy of
``runtime.compile_model(cfg, params, backend="lut", recipe=...)`` on the
2-class KWT-Tiny task — identical recipe, identical int8/ROM footprint,
only the *training* differs.

Rows per weight exponent (paper Table V best 2^6, plus the aggressive
2^1 / 2^0 rows where the eq-9 grid actually bites — at 2^6 this
surrogate's PTQ is near-lossless, exactly the paper's regime where
retraining matters most is the coarse-grid one):

  * ``ptq``     — float training, post-hoc eq-9 cast (the old pipeline)
  * ``qat``     — repro.qat fine-tune (fake-quant forward, STE), best
                  checkpoint by validation fold
  * ``qat_kd``  — QAT + distillation from a float KWT-1 teacher
                  (35-class fine-grained surrogate, reduced head,
                  surgeon-shrunk + retrained)

Accuracies are reported on a test fold disjoint from both the training
stream and the checkpoint-selection fold.

Usage:  PYTHONPATH=src python -m benchmarks.qat_bench [--quick]
            [--out BENCH_qat.json]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro import qat, runtime
from repro.configs import registry
from repro.data import pipeline
from repro.models import kwt
from repro.qat import distill as D


def make_eval(cfg, exec_cfg, seed, n):
    fwd = jax.jit(lambda p, x: kwt.forward(p, x, exec_cfg))
    batches = pipeline.gsc_eval_set(seed, n=n, input_dim=cfg.input_dim)

    def acc(deployed_params):
        correct = total = 0
        for b in batches:
            pred = jnp.argmax(fwd(deployed_params, b["mfcc"]), -1)
            correct += int(jnp.sum(pred == b["labels"]))
            total += int(b["labels"].size)
        return correct / total

    return acc


def build_teacher(cfg, steps, keep_layers, seed=0):
    """Float KWT-1 on the student grid -> surgeon shrink -> retrain ->
    35->2 head reduction (the qat.distill pipeline)."""
    tcfg = D.teacher_config(registry.get("kwt-1").config, cfg)
    tparams = D.train_teacher(tcfg, steps, seed=seed + 1, lr=1.5e-3)
    if keep_layers and keep_layers < tcfg.n_layers:
        cal = [pipeline.keyword_batch(seed + 2, i, batch=64,
                                      input_dim=tcfg.input_dim,
                                      n_classes=tcfg.n_classes)
               for i in range(2)]
        tparams, tcfg = D.shrink_teacher(tparams, tcfg, keep_layers, cal)
        tparams = D.train_teacher(tcfg, steps, seed=seed + 1, lr=1.5e-3,
                                  init_params=tparams)
    tparams = D.reduce_head(tparams)
    return D.DistillSpec(tparams, tcfg.with_(n_classes=cfg.n_classes),
                         alpha=0.3, temperature=2.0)


def bench_qat(out_path="BENCH_qat.json", *, float_steps=300, qat_steps=200,
              teacher_steps=300, teacher_keep=4, eval_n=2048,
              exponents=(6, 1, 0), seed=0):
    cfg = registry.get("kwt-tiny").config
    t_start = time.time()
    # distill.train_teacher is the generic float kwt training loop; on
    # the student config it trains the 2-class baseline
    fparams = D.train_teacher(cfg, float_steps, seed=seed, lr=3e-3)
    lut_cfg = runtime.get_backend("lut").configure(cfg)
    test = make_eval(cfg, lut_cfg, 0, eval_n)          # test fold
    acc_float = make_eval(cfg, cfg, 0, eval_n)(fparams)
    print(f"float accuracy: {acc_float:.3f}")

    distill = build_teacher(cfg, teacher_steps, teacher_keep, seed=seed)
    t_acc = make_eval(cfg, distill.teacher_cfg, 0, eval_n)(
        distill.teacher_params)
    print(f"teacher (reduced head) accuracy: {t_acc:.3f}")

    variants = []
    ok_qat = ok_kd = True
    for wexp in exponents:
        recipe = runtime.QuantRecipe.from_config(cfg, weight_exponent=wexp)
        eng = runtime.compile_model(cfg, fparams, backend="lut",
                                    recipe=recipe)
        # packed_rom_bytes: the TRUE packed weight image (Engine.rom_bytes
        # since the integer-resident-QTensor PR); lut_bytes: the 2.69 kB
        # LUT bank that rom_bytes used to report.
        packed_rom = eng.rom_bytes
        lut_bytes = eng.lut_bytes

        def row(name, acc):
            variants.append({
                "name": name, "weight_exponent": wexp,
                "accuracy": round(acc, 4),
                "packed_rom_bytes": packed_rom, "lut_bytes": lut_bytes,
                "recipe": recipe.to_dict()})
            print(f"w=2^{wexp} {name:7s}: {acc:.3f}  "
                  f"(rom {packed_rom} B, lut {lut_bytes} B)")

        acc_ptq = test(recipe.apply(fparams))
        row("ptq", acc_ptq)

        spec = qat.QATSpec(recipe)
        val = make_eval(cfg, lut_cfg, 5, eval_n)
        qp, qs = qat.finetune_qat(cfg, fparams, spec, qat_steps, seed=seed,
                                  lr=3e-3 if wexp <= 1 else 1e-3,
                                  select_fn=val)
        ex = qat.export(qp, spec, qs)
        acc_qat = test(ex.deployed_params)
        row("qat", acc_qat)
        ok_qat &= acc_qat >= acc_ptq - 0.02

        kd_spec = qat.QATSpec(recipe, qat.QATConfig(), distill=distill)
        qp, qs = qat.finetune_qat(cfg, fparams, kd_spec, qat_steps,
                                  seed=seed, fine_classes=35,
                                  lr=3e-3 if wexp <= 1 else 1e-3,
                                  select_fn=val)
        ex = qat.export(qp, kd_spec, qs)
        acc_kd = test(ex.deployed_params)
        row("qat_kd", acc_kd)
        ok_kd &= acc_kd >= acc_ptq - 0.02

    from repro import perf

    report = {
        "arch": "kwt-tiny", "task": "2-class keyword surrogate",
        "eval_n": eval_n, "float_steps": float_steps,
        "qat_steps": qat_steps, "float_accuracy": round(acc_float, 4),
        "teacher_accuracy": round(t_acc, 4),
        "device": jax.default_backend(),
        "provenance": perf.provenance(),
        "wall_s": round(time.time() - t_start, 1),
        "acceptance": {"qat_ge_ptq": bool(ok_qat),
                       "kd_ge_ptq": bool(ok_kd)},
        "variants": variants,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out_path} (acceptance: qat_ge_ptq={ok_qat}, "
          f"kd_ge_ptq={ok_kd})", file=sys.stderr)
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (fewer steps, smaller eval)")
    ap.add_argument("--out", default="BENCH_qat.json")
    args = ap.parse_args()
    runtime.enable_compile_cache()
    if args.quick:
        report = bench_qat(args.out, float_steps=150, qat_steps=100,
                           teacher_steps=150, eval_n=1024,
                           exponents=(6, 0))
    else:
        report = bench_qat(args.out)
    if not all(report["acceptance"].values()):
        print("FAIL: a QAT variant regressed below PTQ", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
