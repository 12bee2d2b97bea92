"""Fault-tolerant training launcher.

Demonstrates, at host scale (CPU devices) with the exact production code
paths (steps.make_train_step + sharded pjit + checkpoint manager):
  * deterministic stateless-seeded data (restart-exact resume),
  * periodic async checkpointing (atomic rename),
  * crash/preemption recovery: --fail-at-step N injects a failure; rerunning
    the same command resumes from the newest complete checkpoint,
  * straggler watchdog: EWMA step-time monitor flags slow steps (on a real
    fleet this feeds the reslicing controller),
  * elastic restart: --data/--model may differ across runs; restore
    re-shards against the new mesh.

Usage (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
      --steps 20 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.checkpoint import manager
from repro.configs import registry
from repro.configs.base import ShapeSpec
from repro.data import pipeline
from repro.dist import ctx
from repro.launch import mesh as meshlib
from repro.launch import steps


class StragglerMonitor:
    """EWMA step-time watchdog (DESIGN.md §3 fault-tolerance)."""

    def __init__(self, alpha=0.2, threshold=2.5):
        self.alpha, self.threshold = alpha, threshold
        self.ewma = None
        self.flagged = []

    def observe(self, step, dt):
        if self.ewma is None:
            self.ewma = dt
            return False
        slow = dt > self.threshold * self.ewma
        if slow:
            self.flagged.append((step, dt, self.ewma))
            print(f"[straggler] step {step}: {dt*1e3:.1f}ms vs "
                  f"EWMA {self.ewma*1e3:.1f}ms -> would trigger reslicing")
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return slow


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--data", type=int, default=1, help="mesh data axis")
    ap.add_argument("--model", type=int, default=1, help="mesh model axis")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="inject a crash at this step (recovery demo)")
    ap.add_argument("--compressed-grads", action="store_true",
                    help="int8 error-feedback gradient sync on the mesh's "
                         "slow axis (dist.compress)")
    ap.add_argument("--per-channel-scales", action="store_true",
                    help="per-channel payload scales for --compressed-grads")
    ap.add_argument("--grad-bits", type=int, default=8, choices=(4, 8),
                    help="wire width for --compressed-grads payloads "
                         "(4: nibble-packed via the shared core.quant "
                         "codec, half the int8 wire bytes)")
    ap.add_argument("--qat", action="store_true",
                    help="quantisation-aware training: the loss forward "
                         "runs eq-9 fake-quant params under --qat-backend's "
                         "LUT modes (repro.qat)")
    ap.add_argument("--qat-backend", default="lut",
                    help="runtime backend whose numerics the QAT loss runs")
    ap.add_argument("--qat-start-step", type=int, default=0,
                    help="float warm-up steps before fake-quant activates")
    ap.add_argument("--qat-learn-exponent", action="store_true",
                    help="recalibrate the weight exponent from the shadow "
                         "weights until --qat-freeze-exponent-step")
    ap.add_argument("--qat-freeze-exponent-step", type=int, default=0,
                    help="freeze the learned exponent after this step "
                         "(0: keep recalibrating every step)")
    ap.add_argument("--distill-teacher-arch", default=None,
                    help="KWT only: float teacher arch for KD during QAT "
                         "(e.g. kwt-1; head is reduced to the student's "
                         "classes)")
    ap.add_argument("--distill-teacher-steps", type=int, default=200,
                    help="float training steps for the inline KD teacher")
    ap.add_argument("--distill-alpha", type=float, default=0.5)
    ap.add_argument("--distill-temp", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()

    entry = registry.get(args.arch)
    cfg = entry.smoke if args.smoke else entry.config
    shape = ShapeSpec("custom", args.seq_len, args.global_batch, "train")
    mesh = meshlib.make_host_mesh(args.data, args.model)
    dp = meshlib.dp_axes(mesh)
    hp = dataclasses.replace(steps.hparams_for(cfg), lr=1e-3,
                             warmup_steps=max(2, args.steps // 10),
                             total_steps=max(args.steps, 10))
    mod = steps.model_module(cfg)

    qat_spec = None
    fine_classes = None
    if args.qat:
        from repro import qat as qat_mod
        from repro.runtime import QuantRecipe
        distill = None
        if args.distill_teacher_arch:
            if cfg.family != "kwt":
                ap.error("--distill-teacher-arch is the KWT KD path "
                         "(paper §III); LM QAT runs without a teacher")
            from repro.qat import distill as distill_mod
            tcfg = distill_mod.teacher_config(
                registry.get(args.distill_teacher_arch).config, cfg)
            print(f"[distill] training float teacher {tcfg.name} "
                  f"({args.distill_teacher_steps} steps, "
                  f"{tcfg.n_classes} classes)")
            tparams = distill_mod.train_teacher(
                tcfg, args.distill_teacher_steps, seed=args.seed + 1)
            tparams = distill_mod.reduce_head(tparams)
            distill = distill_mod.DistillSpec(
                tparams, tcfg.with_(n_classes=cfg.n_classes),
                alpha=args.distill_alpha, temperature=args.distill_temp)
            # KD draws the fine-grained surrogate (coarsened to the
            # student's classes) so the teacher stays on-distribution
            fine_classes = tcfg.n_classes
        qat_spec = qat_mod.QATSpec(
            QuantRecipe.from_config(cfg),
            qat_mod.QATConfig(
                backend=args.qat_backend, start_step=args.qat_start_step,
                learn_exponent=args.qat_learn_exponent,
                freeze_exponent_step=args.qat_freeze_exponent_step),
            distill=distill)
        print(f"[qat] recipe {qat_spec.recipe} under backend="
              f"{args.qat_backend}")

    from jax.sharding import NamedSharding
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                        steps.param_pspecs(cfg),
                        is_leaf=lambda x: isinstance(
                            x, jax.sharding.PartitionSpec))

    with ctx.mesh_context(mesh, dp):
        params = jax.jit(
            lambda k: mod.init_params(cfg, k),
            out_shardings=p_sh)(jax.random.PRNGKey(args.seed))
        from repro.optim import adamw
        opt_state = adamw.init(params, hp)

        from repro.dist import compress
        err = compress.init_error_state(params) if args.compressed_grads \
            else None
        qstate = None
        if qat_spec is not None:
            from repro import qat as qat_mod
            qstate = qat_mod.init_qat_state(qat_spec)

        start_step = 0
        if args.ckpt_dir:
            # resume from the newest step complete in EVERY tree: the opt
            # save is async, so a crash can leave params one step ahead;
            # with --compressed-grads the error-feedback residuals are a
            # third tree (dropping them would break the telescoping
            # drift bound at every restart), and --qat adds the QAT state
            # (float shadow weights are the params tree; the learned
            # exponent + step counter must restore with them or the
            # exported recipe would drift across restarts)
            cand = [manager.latest_step(args.ckpt_dir),
                    manager.latest_step(args.ckpt_dir + "/opt")]
            if args.compressed_grads:
                cand.append(manager.latest_step(args.ckpt_dir + "/err"))
            if qstate is not None:
                cand.append(manager.latest_step(args.ckpt_dir + "/qat"))
            if cand[0] is not None and any(c is None for c in cand[1:]):
                print(f"[restore] params checkpoint at step {cand[0]} has no "
                      "complete optimizer/error state — starting from step 0")
            latest = None if any(c is None for c in cand) else min(cand)
            if latest is not None:
                print(f"[restore] resuming from step {latest}")
                params = manager.restore(args.ckpt_dir, latest, params)
                opt_state = manager.restore(
                    args.ckpt_dir + "/opt", latest, opt_state)
                if args.compressed_grads:
                    err = manager.restore(
                        args.ckpt_dir + "/err", latest, err)
                if qstate is not None:
                    qstate = manager.restore(
                        args.ckpt_dir + "/qat", latest, qstate)
                start_step = latest

        sync_mesh = mesh if args.compressed_grads else None
        train_step = jax.jit(
            steps.make_train_step(cfg, shape, hp, n_micro=1,
                                  sync_mesh=sync_mesh,
                                  sync_per_channel=args.per_channel_scales,
                                  sync_bits=args.grad_bits,
                                  qat=qat_spec),
            donate_argnums=(0, 1))

        mon = StragglerMonitor()
        pending = None
        for step in range(start_step, args.steps):
            if step == args.fail_at_step:
                raise RuntimeError(
                    f"[injected failure] node lost at step {step} — rerun "
                    "the same command to recover from the last checkpoint")
            if cfg.family == "kwt":
                batch = pipeline.keyword_batch(
                    args.seed, step, batch=args.global_batch,
                    input_dim=cfg.input_dim,
                    n_classes=fine_classes or cfg.n_classes)
                if fine_classes:
                    batch = {"mfcc": batch["mfcc"],
                             "labels": batch["labels"] % cfg.n_classes}
            elif cfg.family == "encdec":
                batch = _whisper_batch(args, cfg, step)
            else:
                batch = pipeline.lm_batch(
                    args.seed, step, global_batch=args.global_batch,
                    seq_len=args.seq_len, vocab_size=cfg.vocab_size)
            t0 = time.time()
            if qstate is not None and args.compressed_grads:
                params, opt_state, qstate, err, metrics = train_step(
                    params, opt_state, qstate, err, batch)
            elif qstate is not None:
                params, opt_state, qstate, metrics = train_step(
                    params, opt_state, qstate, batch)
            elif args.compressed_grads:
                params, opt_state, err, metrics = train_step(
                    params, opt_state, err, batch)
            else:
                params, opt_state, metrics = train_step(params, opt_state,
                                                        batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            mon.observe(step, dt)
            print(f"step {step:5d} loss {loss:.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms",
                  flush=True)
            assert np.isfinite(loss), "loss diverged"
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                if pending is not None:
                    pending.join()
                manager.save(args.ckpt_dir, step + 1, params, blocking=True)
                if err is not None:
                    manager.save(args.ckpt_dir + "/err", step + 1, err,
                                 blocking=True)
                if qstate is not None:
                    manager.save(args.ckpt_dir + "/qat", step + 1, qstate,
                                 blocking=True)
                pending = manager.save(args.ckpt_dir + "/opt", step + 1,
                                       opt_state, blocking=False)
        if pending is not None:
            pending.join()
    if qat_spec is not None:
        from repro import qat as qat_mod
        ex = qat_mod.export(params, qat_spec, qstate)
        print(f"[qat] exported recipe: {ex.recipe}; packed int bytes "
              f"{ex.quantized_bytes[0]} + float {ex.quantized_bytes[1]}")
    print("training complete.")
    return params


def _whisper_batch(args, cfg, step):
    key = jax.random.fold_in(jax.random.PRNGKey(args.seed + 77), step)
    k1, k2 = jax.random.split(key)
    frames = jax.random.normal(
        k1, (args.global_batch, cfg.enc_seq, cfg.d_model))
    toks = jax.random.randint(
        k2, (args.global_batch, args.seq_len + 1), 0, cfg.vocab_size)
    return {"frames": frames, "tokens": toks[:, :-1], "labels": toks[:, 1:]}


if __name__ == "__main__":
    main()
