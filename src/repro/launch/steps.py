"""Step builders: per (architecture x shape) train / prefill / decode steps,
their input ShapeDtypeStructs, and their sharding trees.

This module is the glue between configs, models, optim and the mesh: the
launchers (train.py / serve.py) and the dry-run (dryrun.py) all build their
jitted programs here, so the lowered-and-compiled artifact in the dry-run
is exactly the program a real fleet would run.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch import mesh as meshlib
from repro.models import encdec as E
from repro.models import transformer as T
from repro.optim import adamw
from repro.telemetry import annotate

# grad-accumulation microbatch counts chosen so per-device activation
# checkpoints fit v5e HBM (derivation in DESIGN.md §3 memory table)
MICROBATCHES = {
    ("nemotron-4-340b", "train_4k"): 16,
    ("chameleon-34b", "train_4k"): 16,
    ("qwen2.5-14b", "train_4k"): 8,
    ("granite-8b", "train_4k"): 8,
    ("deepseek-moe-16b", "train_4k"): 8,
    ("granite-moe-3b-a800m", "train_4k"): 2,
    ("internlm2-1.8b", "train_4k"): 2,
    ("rwkv6-3b", "train_4k"): 4,
    ("hymba-1.5b", "train_4k"): 4,
    ("whisper-large-v3", "train_4k"): 4,
}

# archs whose train activations additionally shard the SEQUENCE dim over
# the TP axis (Megatron-SP style) — required to fit HBM at 96L x d=18432
SEQ_SHARD = {("nemotron-4-340b", "train_4k"), ("chameleon-34b", "train_4k"),
             ("nemotron-4-340b", "prefill_32k"), ("chameleon-34b", "prefill_32k")}


def seq_axis_for(cfg: ModelConfig, shape: ShapeSpec):
    return "model" if (cfg.name, shape.name) in SEQ_SHARD else None


# archs whose optimizer state must be int8 to fit a pod (DESIGN.md §3)
INT8_MOMENT_ARCHS = {"nemotron-4-340b", "deepseek-moe-16b", "chameleon-34b",
                     "qwen2.5-14b"}


def hparams_for(cfg: ModelConfig) -> adamw.HParams:
    return adamw.HParams(int8_moments=cfg.name in INT8_MOMENT_ARCHS)


def microbatches(cfg: ModelConfig, shape: ShapeSpec, mesh=None) -> int:
    n = MICROBATCHES.get((cfg.name, shape.name), 1)
    if mesh is not None:
        # each microbatch must still split over every DP device
        dp_total = 1
        for a in meshlib.dp_axes(mesh):
            dp_total *= mesh.shape[a]
        n = max(1, min(n, shape.global_batch // dp_total))
        while shape.global_batch % (n * dp_total):
            n -= 1
    return n


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs — weak-type-correct, no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """Model inputs for one step of the given shape (no state/params)."""
    gb, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if cfg.family == "encdec":
        frames = jax.ShapeDtypeStruct((gb, cfg.enc_seq, cfg.d_model),
                                      jnp.dtype(cfg.dtype))
        if shape.kind == "train":
            return {"frames": frames,
                    "tokens": jax.ShapeDtypeStruct((gb, s), i32),
                    "labels": jax.ShapeDtypeStruct((gb, s), i32)}
        if shape.kind == "prefill":
            return {"frames": frames,
                    "tokens": jax.ShapeDtypeStruct((gb, s), i32)}
        return {"token": jax.ShapeDtypeStruct((gb,), i32)}
    if shape.kind == "train":
        return {"tokens": jax.ShapeDtypeStruct((gb, s), i32),
                "labels": jax.ShapeDtypeStruct((gb, s), i32)}
    if shape.kind == "prefill":
        return {"tokens": jax.ShapeDtypeStruct((gb, s), i32)}
    return {"token": jax.ShapeDtypeStruct((gb,), i32)}


def batch_pspec(cfg: ModelConfig, shape: ShapeSpec, dp) -> dict:
    bp = P(dp)
    b2 = P(dp, None)
    b3 = P(dp, None, None)
    if cfg.family == "encdec":
        if shape.kind == "train":
            return {"frames": b3, "tokens": b2, "labels": b2}
        if shape.kind == "prefill":
            return {"frames": b3, "tokens": b2}
        return {"token": bp}
    if shape.kind == "train":
        return {"tokens": b2, "labels": b2}
    if shape.kind == "prefill":
        return {"tokens": b2}
    return {"token": bp}


def dp_for(shape: ShapeSpec, mesh):
    """DP axes for this cell; None when the global batch cannot split
    across every DP device (e.g. long_500k's batch of 1 -> replicated)."""
    dp = meshlib.dp_axes(mesh)
    tot = 1
    for a in dp:
        tot *= mesh.shape[a]
    return dp if shape.global_batch % tot == 0 else None


def model_module(cfg: ModelConfig):
    if cfg.family == "kwt":
        from repro.models import kwt as K
        return K
    return E if cfg.family == "encdec" else T


def params_shape(cfg: ModelConfig):
    mod = model_module(cfg)
    return jax.eval_shape(lambda k: mod.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def param_pspecs(cfg: ModelConfig):
    return model_module(cfg).param_specs(cfg)


def decode_state_shape(cfg: ModelConfig, shape: ShapeSpec):
    mod = model_module(cfg)
    return jax.eval_shape(
        lambda: mod.init_decode_state(cfg, shape.global_batch, shape.seq_len))


def decode_state_pspecs(cfg: ModelConfig, dp, tp_size=16):
    return model_module(cfg).decode_state_specs(cfg, dp, tp_size)


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------

def _loss(cfg):
    return model_module(cfg).loss_fn


def make_train_step(cfg: ModelConfig, shape: ShapeSpec, hp=None, n_micro=None,
                    sync_mesh=None, sync_per_channel=False, sync_bits=8,
                    qat=None):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    Gradient accumulation over ``n_micro`` microbatches via lax.scan;
    grads are averaged in f32, then one AdamW update.

    ``sync_mesh`` enables error-feedback gradient compression on the
    mesh's slow axis (``dist.compress.compressed_grad_sync``; the ROADMAP
    follow-up from the repro.dist PR): the step then threads the residual
    state — ``(params, opt_state, err, batch) -> (params, opt_state, err,
    metrics)`` with ``err`` from ``compress.init_error_state``.
    ``sync_per_channel`` selects per-channel payload scales; ``sync_bits``
    the wire width (4 -> nibble-packed payloads via the shared
    ``core.quant`` codec, half the int8 wire bytes).

    ``qat`` (a ``repro.qat.train.QATSpec``) switches the step to
    quantisation-aware training: the loss forward runs eq-9 fake-quant
    params under a runtime Backend's LUT modes while AdamW updates the
    float shadow weights; the step then additionally threads the QAT
    state — ``(params, opt_state, qstate, [err,] batch) -> (params,
    opt_state, qstate, [err,] metrics)`` with ``qstate`` from
    ``qat.init_qat_state``.  Composes with ``sync_mesh``.
    """
    if qat is not None:
        from repro.qat import train as qat_train
        return qat_train.make_qat_train_step(
            cfg, shape, hp=hp, n_micro=n_micro, sync_mesh=sync_mesh,
            sync_per_channel=sync_per_channel, sync_bits=sync_bits, qat=qat)
    hp = hp or hparams_for(cfg)
    n_micro = n_micro or microbatches(cfg, shape)
    loss_fn = _loss(cfg)

    def split_micro(batch):
        def f(x):
            b = x.shape[0]
            return x.reshape((n_micro, b // n_micro) + x.shape[1:])
        return jax.tree.map(f, batch)

    def compute_grads(params, batch):
        if n_micro == 1:
            return jax.value_and_grad(loss_fn)(params, batch, cfg)
        micro = split_micro(batch)

        def body(acc, mb):
            l, g = jax.value_and_grad(loss_fn)(params, mb, cfg)
            acc = jax.tree.map(
                lambda a, gg: a + gg.astype(jnp.float32) / n_micro,
                acc, g)
            return acc, l

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        grads, losses = jax.lax.scan(body, zeros, micro)
        return jnp.mean(losses), grads

    def finish(loss, grads, opt_state, params):
        # telemetry.annotate stages (jax.named_scope) name the grads /
        # grad_sync / optimizer regions in XLA profiles; metadata-only.
        with annotate("optimizer"):
            new_params, new_opt, metrics = adamw.update(
                grads, opt_state, params, hp, scan_stacked=cfg.scan_layers)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    if sync_mesh is None:
        def train_step(params, opt_state, batch):
            with annotate("grads"):
                loss, grads = compute_grads(params, batch)
            return finish(loss, grads, opt_state, params)
        return train_step

    from repro.dist import compress

    def train_step_synced(params, opt_state, err, batch):
        with annotate("grads"):
            loss, grads = compute_grads(params, batch)
        with annotate("grad_sync"):
            grads, err = compress.compressed_grad_sync(
                grads, err, sync_mesh, per_channel=sync_per_channel,
                bits=sync_bits)
        new_params, new_opt, metrics = finish(loss, grads, opt_state, params)
        return new_params, new_opt, err, metrics

    return train_step_synced


def make_prefill_step(cfg: ModelConfig, shape: ShapeSpec):
    mod = model_module(cfg)

    if cfg.family == "encdec":
        def step(params, state, batch):
            return mod.prefill(params, batch["frames"], batch["tokens"],
                               cfg, state)
        return step

    def step(params, state, batch):
        return mod.prefill(params, batch["tokens"], cfg, state)
    return step


def make_decode_step(cfg: ModelConfig, shape: ShapeSpec):
    mod = model_module(cfg)

    def step(params, state, batch):
        return mod.decode_step(params, batch["token"], cfg, state)
    return step


# ---------------------------------------------------------------------------
# Jitted + sharded program assembly
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Program:
    """A fully-specified (fn, in_shardings, example_args) unit, ready to
    ``jax.jit(...).lower(*args)``."""
    name: str
    fn: Any
    args: tuple          # ShapeDtypeStructs (or arrays)
    shardings: tuple     # same-structure NamedSharding trees
    multiplier: float = 1.0   # dry-run cost multiplier (DESIGN.md §4)
    donate: tuple = ()
    seq_axis: str | None = None   # Megatron-SP activation sharding
    dp: Any = "auto"              # DP axes override (None = replicated batch)


def _named(mesh, tree):
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec), tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_step_program(cfg: ModelConfig, shape: ShapeSpec, mesh) -> Program:
    """The full (while-loop-containing) step: the deployable artifact whose
    compile + memory_analysis the dry-run must pass."""
    dp = dp_for(shape, mesh)
    batch_sds = input_specs(cfg, shape)
    batch_sh = _named(mesh, batch_pspec(cfg, shape, dp))
    p_sds = params_shape(cfg)
    p_sh = _named(mesh, param_pspecs(cfg))
    if shape.kind == "train":
        hp = hparams_for(cfg)
        opt_sds = jax.eval_shape(functools.partial(adamw.init, hp=hp), p_sds)
        opt_sh = _named(mesh, adamw.opt_state_specs(param_pspecs(cfg), hp))
        fn = make_train_step(cfg, shape, hp,
                             n_micro=microbatches(cfg, shape, mesh))
        return Program(f"{cfg.name}:{shape.name}:train", fn,
                       (p_sds, opt_sds, batch_sds), (p_sh, opt_sh, batch_sh),
                       donate=(0, 1), seq_axis=seq_axis_for(cfg, shape), dp=dp)
    state_sds = decode_state_shape(cfg, shape)
    state_sh = _named(mesh, decode_state_pspecs(cfg, dp,
                                                mesh.shape["model"]))
    if shape.kind == "prefill":
        fn = make_prefill_step(cfg, shape)
        return Program(f"{cfg.name}:{shape.name}:prefill", fn,
                       (p_sds, state_sds, batch_sds), (p_sh, state_sh, batch_sh),
                       donate=(1,), seq_axis=seq_axis_for(cfg, shape), dp=dp)
    fn = make_decode_step(cfg, shape)
    return Program(f"{cfg.name}:{shape.name}:decode", fn,
                   (p_sds, state_sds, batch_sds), (p_sh, state_sh, batch_sh),
                   donate=(1,), dp=dp)


def lower_program(prog: Program, mesh, seq_axis=None):
    from repro.dist import ctx
    seq_axis = seq_axis or prog.seq_axis
    dp = prog.dp if prog.dp != "auto" else meshlib.dp_axes(mesh)
    with ctx.mesh_context(mesh, dp, seq_axis):
        jitted = jax.jit(prog.fn, in_shardings=prog.shardings,
                         donate_argnums=prog.donate)
        return jitted.lower(*prog.args)


# ---------------------------------------------------------------------------
# Cost decomposition (DESIGN.md §4)
#
# XLA's cost_analysis counts a while-loop body ONCE, so the scanned-layer
# (and scanned-chunk) costs must be reconstructed from while-free component
# programs:   total = sum_i multiplier_i x cost(component_i).
#
# dense/moe/whisper:  outside(L=0) + L x block          (exact)
# rwkv:               outside + L x [c1 + (S/c - 1)(c2 - c1)]   (exact: every
#                     sub-block is linear in S at fixed chunk c)
# hybrid (hymba):     rwkv-style linear part + windowed-attention correction
#                     via standalone attention programs at full S (exact)
# ---------------------------------------------------------------------------

from repro.models import layers as L  # noqa: E402


def _block_sds(cfg):
    return jax.eval_shape(
        lambda k: T.block_params(cfg, k), jax.random.PRNGKey(0))


def _x_sds(cfg, tokens_b, s):
    return jax.ShapeDtypeStruct((tokens_b, s, cfg.d_model), jnp.dtype(cfg.dtype))


def _block_fwd_fn(cfg, s, *, train):
    """Single-block apply (or fwd+bwd when train) on [B,s,D]."""
    def fwd(bp, x):
        state = T._fresh_state(cfg, x.shape[0])
        y, _ = T.apply_block(bp, x, cfg, state, positions=jnp.arange(s))
        return y

    if not train:
        return fwd

    def loss(bp, x):
        return jnp.sum(fwd(bp, x).astype(jnp.float32))

    body = jax.checkpoint(loss) if cfg.remat else loss
    return jax.grad(body, argnums=(0, 1))


def _attn_only_fn(cfg, s, *, train):
    """Standalone windowed attention on [B,s,D] (hymba correction term)."""
    def fwd(ap, x):
        y, _ = L.apply_attention(ap, x, cfg, positions=jnp.arange(s))
        return y

    if not train:
        return fwd

    def loss(ap, x):
        return jnp.sum(fwd(ap, x).astype(jnp.float32))

    body = jax.checkpoint(loss) if cfg.remat else loss
    return jax.grad(body, argnums=(0, 1))


def _attn_sds(cfg):
    return jax.eval_shape(
        lambda k: L.attention_params(cfg, k), jax.random.PRNGKey(0))


def _decode_block_fn(cfg, shape):
    w = cfg.sliding_window
    def fn(bp, x, state, idx):
        if cfg.family == "hybrid":
            return T.apply_block(bp, x, cfg, state,
                                 positions=idx + jnp.arange(1),
                                 cache_index=jnp.mod(idx, w),
                                 kv_len_valid=jnp.minimum(idx + 1, w),
                                 ring=True)
        if cfg.family == "rwkv":
            return T.apply_block(bp, x, cfg, state, positions=None)
        return T.apply_block(bp, x, cfg, state,
                             positions=idx + jnp.arange(x.shape[1]),
                             cache_index=idx, kv_len_valid=idx + x.shape[1])
    return fn


def _per_layer_decode_state_sds(cfg, shape):
    mod = model_module(cfg)
    full = jax.eval_shape(
        lambda: mod.init_decode_state(cfg, shape.global_batch, shape.seq_len))
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), full["layers"])


def _per_layer_decode_state_spec(cfg, dp, tp_size=16):
    full = model_module(cfg).decode_state_specs(cfg, dp, tp_size)
    return jax.tree.map(lambda spec: P(*tuple(spec)[1:]), full["layers"],
                        is_leaf=lambda x: isinstance(x, P))


def cost_programs(cfg: ModelConfig, shape: ShapeSpec, mesh) -> list:
    """While-free component programs + multipliers for this cell."""
    dp = dp_for(shape, mesh)
    progs = []
    for_dp = dp
    x_spec = _named(mesh, P(dp, None, None))
    gb, s = shape.global_batch, shape.seq_len
    c = 16  # recurrence chunk (rwkv.CHUNK == ssm.CHUNK == 16)

    if cfg.family == "encdec":
        progs.extend(_whisper_cost_programs(cfg, shape, mesh))
        return progs

    if shape.kind == "train":
        n_micro = microbatches(cfg, shape, mesh)
        mb = gb // n_micro
        hp = hparams_for(cfg)
        block_sh = _named(mesh, T.block_specs(cfg))
        if cfg.family in ("dense", "moe"):
            progs.append(Program(
                "block_fwdbwd", _block_fwd_fn(cfg, s, train=True),
                (_block_sds(cfg), _x_sds(cfg, mb, s)), (block_sh, x_spec),
                multiplier=cfg.n_layers * n_micro,
                seq_axis=seq_axis_for(cfg, shape)))
        else:
            f1 = _block_fwd_fn(cfg, c, train=True)
            f2 = _block_fwd_fn(cfg, 2 * c, train=True)
            # linear-in-S two-point: c1 + (S/c - 1)(c2 - c1), applied by the
            # dry-run combiner via paired multipliers.
            m_hi = (s // c - 1) * cfg.n_layers * n_micro
            m_lo = cfg.n_layers * n_micro - m_hi
            progs.append(Program("block_fwdbwd@c",
                                 f1, (_block_sds(cfg), _x_sds(cfg, mb, c)),
                                 (block_sh, x_spec), multiplier=m_lo))
            progs.append(Program("block_fwdbwd@2c",
                                 f2, (_block_sds(cfg), _x_sds(cfg, mb, 2 * c)),
                                 (block_sh, x_spec), multiplier=m_hi))
            if cfg.family == "hybrid":
                progs.extend(_hymba_attn_correction(
                    cfg, mesh, mb, s, c, cfg.n_layers * n_micro, train=True))
        cfg0 = cfg.with_(n_layers=0)
        mb_shape = dataclasses.replace(shape, global_batch=mb)
        outside = make_train_like_loss(cfg0)
        progs.append(Program(
            "outside_fwdbwd", outside,
            (params_shape(cfg0), input_specs(cfg0, mb_shape)),
            (_named(mesh, param_pspecs(cfg0)),
             _named(mesh, batch_pspec(cfg0, mb_shape, dp))),
            multiplier=n_micro))
        # optimizer update over the full parameter tree
        def opt_fn(params, opt_state, grads):
            return adamw.update(grads, opt_state, params, hp,
                                scan_stacked=cfg.scan_layers)
        p_sds = params_shape(cfg)
        g_sds = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), p_sds)
        opt_sds = jax.eval_shape(functools.partial(adamw.init, hp=hp), p_sds)
        p_sh = _named(mesh, param_pspecs(cfg))
        g_sh = p_sh
        opt_sh = _named(mesh, adamw.opt_state_specs(param_pspecs(cfg), hp))
        progs.append(Program("optimizer", opt_fn, (p_sds, opt_sds, g_sds),
                             (p_sh, opt_sh, g_sh), multiplier=1.0))
        return progs

    # ---- inference cells ----
    sq = 1 if shape.is_decode else s
    state_sds = _per_layer_decode_state_sds(cfg, shape)
    state_sh = _named(mesh, _per_layer_decode_state_spec(
        cfg, dp, mesh.shape["model"]))
    idx_sds = jax.ShapeDtypeStruct((), jnp.int32)
    idx_sh = _named(mesh, P())
    del for_dp
    block_sh = _named(mesh, T.block_specs(cfg))
    if cfg.family in ("dense", "moe") or shape.is_decode:
        fn = _decode_block_fn(cfg, shape)
        progs.append(Program(
            "block_step", fn,
            (_block_sds(cfg), _x_sds(cfg, gb, sq), state_sds, idx_sds),
            (block_sh, x_spec, state_sh, idx_sh),
            multiplier=cfg.n_layers))
    else:
        # rwkv/hybrid prefill: two-point in S (state threads through)
        for nm, sc, mult in _two_point(cfg, s, c):
            def fn(bp, x, sc=sc):
                state = T._fresh_state(cfg, x.shape[0])
                y, _ = T.apply_block(bp, x, cfg, state,
                                     positions=jnp.arange(sc))
                return y
            progs.append(Program(nm, fn,
                                 (_block_sds(cfg), _x_sds(cfg, gb, sc)),
                                 (block_sh, x_spec), multiplier=mult))
        if cfg.family == "hybrid":
            progs.extend(_hymba_attn_correction(cfg, mesh, gb, s, c,
                                                cfg.n_layers, train=False))
    cfg0 = cfg.with_(n_layers=0)
    mod = model_module(cfg)

    def outside_fn(params, tokens):
        return mod.forward_no_blocks(params, tokens, cfg0)

    progs.append(Program(
        "outside", outside_fn,
        (params_shape(cfg0), jax.ShapeDtypeStruct((gb, sq), jnp.int32)),
        (_named(mesh, param_pspecs(cfg0)), _named(mesh, P(dp, None))),
        multiplier=1.0))
    for pr in progs:
        pr.dp = dp
    return progs


def _two_point(cfg, s, c):
    """total = L*[c1 + m*(c2 - c1)], m = S/c - 1  ->  coeffs L(1-m), L*m."""
    m = s // c - 1
    return [("block@c", c, cfg.n_layers * (1 - m)),
            ("block@2c", 2 * c, cfg.n_layers * m)]


def make_train_like_loss(cfg0):
    loss_fn = _loss(cfg0)

    def fn(params, batch):
        return jax.grad(lambda p: loss_fn(p, batch, cfg0))(params)
    return fn


def _hymba_attn_correction(cfg, mesh, b, s, c, layer_mult, *, train):
    """Exact windowed-attention term: + attn(full S), - linearised estimate
    (attn@c, attn@2c with the two-point multipliers, negated)."""
    dp = meshlib.dp_axes(mesh)
    x_spec = _named(mesh, P(dp, None, None))
    attn_sh = _named(mesh, L.attention_specs(cfg))
    m = s // c - 1
    out = [Program("attn_full", _attn_only_fn(cfg, s, train=train),
                   (_attn_sds(cfg), _x_sds(cfg, b, s)), (attn_sh, x_spec),
                   multiplier=layer_mult)]
    out.append(Program("attn@c(-)", _attn_only_fn(cfg, c, train=train),
                       (_attn_sds(cfg), _x_sds(cfg, b, c)), (attn_sh, x_spec),
                       multiplier=-float(layer_mult * (1 - m))))
    out.append(Program("attn@2c(-)", _attn_only_fn(cfg, 2 * c, train=train),
                       (_attn_sds(cfg), _x_sds(cfg, b, 2 * c)),
                       (attn_sh, x_spec), multiplier=-float(layer_mult * m)))
    return out


def _whisper_cost_programs(cfg, shape, mesh):
    dp = meshlib.dp_axes(mesh)
    x_spec = _named(mesh, P(dp, None, None))
    progs = []
    train = shape.kind == "train"
    n_micro = microbatches(cfg, shape, mesh) if train else 1
    gb = shape.global_batch
    mb = gb // n_micro
    sq = 1 if shape.is_decode else shape.seq_len

    enc_sh = _named(mesh, E.enc_block_specs(cfg))
    dec_sh = _named(mesh, E.dec_block_specs(cfg))
    enc_sds = jax.eval_shape(lambda k: E.enc_block_params(cfg, k),
                             jax.random.PRNGKey(0))
    dec_sds = jax.eval_shape(lambda k: E.dec_block_params(cfg, k),
                             jax.random.PRNGKey(0))

    def enc_fwd(bp, x):
        return E.apply_enc_block(bp, x, cfg)

    def dec_fwd(bp, x, memory):
        y, _ = E.apply_dec_block(bp, x, cfg,
                                 positions=jnp.arange(x.shape[1]),
                                 memory=memory)
        return y

    if train:
        def enc_fn(bp, x):
            f = lambda bp, x: jnp.sum(enc_fwd(bp, x).astype(jnp.float32))
            f = jax.checkpoint(f) if cfg.remat else f
            return jax.grad(f, argnums=(0, 1))(bp, x)

        def dec_fn(bp, x, memory):
            f = lambda bp, x, m: jnp.sum(dec_fwd(bp, x, m).astype(jnp.float32))
            f = jax.checkpoint(f) if cfg.remat else f
            return jax.grad(f, argnums=(0, 1, 2))(bp, x, memory)
    else:
        enc_fn, dec_fn = enc_fwd, dec_fwd

    if not shape.is_decode:
        progs.append(Program(
            "enc_block", enc_fn,
            (enc_sds, _x_sds(cfg, mb, cfg.enc_seq)), (enc_sh, x_spec),
            multiplier=cfg.n_enc_layers * n_micro))
        progs.append(Program(
            "dec_block", dec_fn,
            (dec_sds, _x_sds(cfg, mb, sq), _x_sds(cfg, mb, cfg.enc_seq)),
            (dec_sh, x_spec, x_spec), multiplier=cfg.n_layers * n_micro))
    else:
        state_sds = _per_layer_decode_state_sds(cfg, shape)
        state_sh = _named(mesh, _per_layer_decode_state_spec(
            cfg, meshlib.dp_axes(mesh), mesh.shape["model"]))
        idx_sds = jax.ShapeDtypeStruct((), jnp.int32)

        def dec_step(bp, x, st, idx):
            return E.apply_dec_block(bp, x, cfg, positions=idx + jnp.arange(1),
                                     state=st, cache_index=idx)
        progs.append(Program(
            "dec_block_step", dec_step,
            (dec_sds, _x_sds(cfg, gb, 1), state_sds, idx_sds),
            (dec_sh, x_spec, state_sh, _named(mesh, P())),
            multiplier=cfg.n_layers))

    # outside: embed/head/loss with zero layers
    cfg0 = cfg.with_(n_layers=0, n_enc_layers=0)
    if train:
        mb_shape = dataclasses.replace(shape, global_batch=mb)
        progs.append(Program(
            "outside_fwdbwd", make_train_like_loss(cfg0),
            (params_shape(cfg0), input_specs(cfg0, mb_shape)),
            (_named(mesh, param_pspecs(cfg0)),
             _named(mesh, batch_pspec(cfg0, mb_shape, meshlib.dp_axes(mesh)))),
            multiplier=n_micro))
        hp = hparams_for(cfg)
        p_sds = params_shape(cfg)
        g_sds = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), p_sds)
        opt_sds = jax.eval_shape(functools.partial(adamw.init, hp=hp), p_sds)
        p_sh = _named(mesh, param_pspecs(cfg))
        opt_sh = _named(mesh, adamw.opt_state_specs(param_pspecs(cfg), hp))

        def opt_fn(params, opt_state, grads):
            return adamw.update(grads, opt_state, params, hp,
                                scan_stacked=cfg.scan_layers)
        progs.append(Program("optimizer", opt_fn, (p_sds, opt_sds, g_sds),
                             (p_sh, opt_sh, p_sh), multiplier=1.0))
    return progs
