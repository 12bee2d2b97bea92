"""Mixture-of-Experts block (granite-moe 40e top-8, deepseek-moe 2+64e top-6).

Dispatch design (DESIGN.md §3): *group-limited capacity* routing executed
under ``shard_map`` — every (data, model) device owns one data-shard's
tokens and one expert slice, so the capacity scatter, the expert FFN and
the combine gather are all device-LOCAL; a single psum over the EP
('model') axis merges the per-slice partial outputs.  GSPMD cannot
partition the token<->expert scatter on its own (measured: 25.8 GB/device
replicated dispatch arrays); explicit locality is the fix — and it is also
the honest EP communication pattern (the psum is the combine all-reduce).

Expert count is padded to a multiple of the EP axis (padded experts are
never routed to).  Expert weights are EP-sharded and replicated over
'data' (experts are fine-grained and small; the memory table in DESIGN.md
shows this fits with int8 optimizer moments).

The router softmax goes through ``approx.softmax`` — under the paper's
technique the router, too, runs on the LUT pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import approx
from repro.models import layers as L

EP_PAD = 16   # pad expert count to a multiple of the EP ('model') axis


def padded_experts(cfg) -> int:
    return -(-cfg.n_experts // EP_PAD) * EP_PAD


def moe_params(cfg, key):
    E, D, Fe = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    Ep = padded_experts(cfg)     # pjit needs the EP dim divisible by 'model';
    dt = jnp.dtype(cfg.dtype)    # padded experts never receive tokens.
    ks = jax.random.split(key, 5)
    p = {
        "router": L.he(ks[0], (D, E), 1.0, jnp.float32),
        "w_gate": L.he(ks[1], (Ep, D, Fe), 1.0, dt),
        "w_up": L.he(ks[2], (Ep, D, Fe), 1.0, dt),
        "w_down": L.he(ks[3], (Ep, Fe, D), 1.0, dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_params(cfg, ks[4],
                                   d_ff=cfg.n_shared_experts * Fe)
    return p


def moe_specs(cfg):
    s = {
        "router": P(None, None),
        # EP over 'model' x FSDP over 'data' on the d_model dim; the
        # shard_map dispatch all-gathers its expert slice over 'data'
        # just-in-time (ZeRO-3 style)
        "w_gate": P(L.TP, L.FSDP, None),
        "w_up": P(L.TP, L.FSDP, None),
        "w_down": P(L.TP, None, L.FSDP),
    }
    if cfg.n_shared_experts:
        s["shared"] = L.mlp_specs(cfg)
    return s


def _capacity(T: int, cfg) -> int:
    c = int(np.ceil(T * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    return max(8, -(-c // 8) * 8)   # round up to a multiple of 8


def _route(xt, router, cfg):
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
    probs = approx.softmax(logits, axis=-1, mode=cfg.softmax_mode,
                           interpret=cfg.kernel_interpret)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)          # [T,k]
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, idx


def _expert_ffn(buf, wg, wu, wd, cfg):
    act = approx.activation(cfg.activation, cfg.act_approx,
                            interpret=cfg.kernel_interpret)
    g = act(jnp.einsum("ecd,edf->ecf", buf, wg))
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", (g * u).astype(buf.dtype), wd)


def _dispatch_ffn_combine(xt, gates, idx, wg, wu, wd, cfg, *, e_lo, e_n, C):
    """Local token->expert scatter, FFN, gather-back for experts
    [e_lo, e_lo+e_n).  All shapes local; no collectives."""
    T, D = xt.shape
    k = cfg.top_k
    fid = idx.reshape(-1)
    mine = jnp.logical_and(fid >= e_lo, fid < e_lo + e_n)
    lid = jnp.clip(fid - e_lo, 0, e_n - 1)
    onehot = jnp.where(mine[:, None],
                       jax.nn.one_hot(lid, e_n, dtype=jnp.int32), 0)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              lid[:, None], axis=1)[:, 0]
    keep = jnp.logical_and(mine, pos < C)
    src = jnp.repeat(xt, k, axis=0)
    buf = jnp.zeros((e_n, C, D), xt.dtype)
    buf = buf.at[lid, jnp.clip(pos, 0, C - 1)].add(
        jnp.where(keep[:, None], src, 0), mode="drop")
    y = _expert_ffn(buf, wg, wu, wd, cfg)
    got = y[lid, jnp.clip(pos, 0, C - 1)]
    got = jnp.where(keep[:, None], got, 0)
    return jnp.sum(got.reshape(T, k, D)
                   * gates.reshape(T, k, 1).astype(xt.dtype), axis=1)


def apply_moe(p, x, cfg):
    from repro.dist import ctx
    B, S, D = x.shape
    T = B * S
    Ep = padded_experts(cfg)
    xt = x.reshape(T, D)

    if not ctx._mesh_active():
        gates, idx = _route(xt, p["router"], cfg)
        out = _dispatch_ffn_combine(
            xt, gates, idx, p["w_gate"], p["w_up"], p["w_down"], cfg,
            e_lo=0, e_n=Ep, C=_capacity(T, cfg))
    else:
        mesh = ctx.current_mesh()
        dp = ctx.dp_axes()
        tp = mesh.shape["model"]
        dp_total = 1
        for a in (dp or ()):
            dp_total *= mesh.shape[a]
        e_n = Ep // tp
        C = _capacity(T // dp_total, cfg)   # group-limited capacity

        def local(xt, router, wg, wu, wd):
            m = jax.lax.axis_index("model")
            # ZeRO-3: gather the FSDP'd d_model dim of my expert slice
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
            gates, idx = _route(xt, router, cfg)
            out = _dispatch_ffn_combine(
                xt, gates, idx, wg, wu, wd, cfg,
                e_lo=m * e_n, e_n=e_n, C=C)
            return jax.lax.psum(out, "model")

        out = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(dp, None), P(None, None),
                      P("model", "data", None), P("model", "data", None),
                      P("model", None, "data")),
            out_specs=P(dp, None),
            check_vma=False,
        )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.n_shared_experts:
        out = out + L.apply_mlp(p["shared"], x, cfg).reshape(T, D)
    return out.reshape(B, S, D).astype(x.dtype)


def load_balance_loss(logits: jnp.ndarray, idx: jnp.ndarray, cfg) -> jnp.ndarray:
    """Switch-style auxiliary load-balancing loss (exposed for training)."""
    E = cfg.n_experts
    probs = jax.nn.softmax(logits, axis=-1)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[..., 0], E), axis=0)
    return E * jnp.sum(me * ce)
