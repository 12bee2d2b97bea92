"""Unified decoder-only LM assembly for all LM-family architectures.

Families: "dense" (granite-8b, internlm2, qwen2.5, nemotron, chameleon),
"moe" (granite-moe, deepseek-moe), "rwkv" (rwkv6-3b), "hybrid" (hymba).
Block math lives in layers.py / moe.py / rwkv.py / ssm.py; this module owns
embedding, layer stacking (lax.scan + per-layer remat), the LM head, loss,
and the prefill/decode state machines.

Decode state ("cache") per family:
  dense/moe : stacked KV caches [L,B,S,KV,Dh] + position index
  rwkv      : stacked recurrence states (S [L,B,H,Dk,Dv], token-shift tails)
  hybrid    : stacked mamba states + *ring-buffer* sliding-window KV caches
              [L,B,W,KV,Dh] (the sub-quadratic long_500k path)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import ctx
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rwkv as R
from repro.models import ssm as S


# ---------------------------------------------------------------------------
# Per-family block param/spec builders
# ---------------------------------------------------------------------------

def block_params(cfg, key):
    if cfg.family in ("dense", "moe"):
        k1, k2 = jax.random.split(key)
        p = {"ln1": L.norm_params(cfg), "ln2": L.norm_params(cfg),
             "attn": L.attention_params(cfg, k1)}
        if cfg.family == "moe":
            p["moe"] = M.moe_params(cfg, k2)
        else:
            p["mlp"] = L.mlp_params(cfg, k2)
        return p
    if cfg.family == "rwkv":
        return R.block_params(cfg, key)
    if cfg.family == "hybrid":
        return S.block_params(cfg, key)
    raise ValueError(cfg.family)


def block_specs(cfg):
    if cfg.family in ("dense", "moe"):
        s = {"ln1": L.norm_specs(cfg), "ln2": L.norm_specs(cfg),
             "attn": L.attention_specs(cfg)}
        if cfg.family == "moe":
            s["moe"] = M.moe_specs(cfg)
        else:
            s["mlp"] = L.mlp_specs(cfg)
        return s
    if cfg.family == "rwkv":
        return R.block_specs(cfg)
    if cfg.family == "hybrid":
        return S.block_specs(cfg)
    raise ValueError(cfg.family)


def apply_block(bp, x, cfg, state, *, positions, cache_index=None,
                kv_len_valid=None, ring=False):
    """Dispatch one block.  state is the per-layer decode state (or None
    for stateless attention training; rwkv/hybrid always carry state)."""
    if cfg.family in ("dense", "moe"):
        if cfg.post_norm:
            a, nc = L.apply_attention(bp["attn"], x, cfg, positions=positions,
                                      cache=state, cache_index=cache_index,
                                      kv_len_valid=kv_len_valid, causal=not ring)
            x = L.apply_norm(bp["ln1"], x + a, cfg)
            f = (M.apply_moe(bp["moe"], x, cfg) if cfg.family == "moe"
                 else L.apply_mlp(bp["mlp"], x, cfg))
            return L.apply_norm(bp["ln2"], x + f, cfg), nc
        h = ctx.unshard_seq(L.apply_norm(bp["ln1"], x, cfg))
        a, nc = L.apply_attention(bp["attn"], h, cfg, positions=positions,
                                  cache=state, cache_index=cache_index,
                                  kv_len_valid=kv_len_valid, causal=not ring)
        x = x + a
        h = ctx.unshard_seq(L.apply_norm(bp["ln2"], x, cfg))
        f = (M.apply_moe(bp["moe"], h, cfg) if cfg.family == "moe"
             else L.apply_mlp(bp["mlp"], h, cfg))
        return x + f, nc
    if cfg.family == "rwkv":
        return R.apply_block(bp, x, cfg, state)
    if cfg.family == "hybrid":
        return S.apply_block(bp, x, cfg, state, positions=positions,
                             cache_index=cache_index,
                             kv_len_valid=kv_len_valid, ring=ring)
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Whole-model params
# ---------------------------------------------------------------------------

def init_params(cfg, key):
    ke, kl, kf = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    blocks = jax.vmap(lambda k: block_params(cfg, k))(
        jax.random.split(kl, cfg.n_layers))
    p = {
        "embed": L.he(ke, (cfg.padded_vocab, cfg.d_model), 1.0, dt),
        "blocks": blocks,
        "ln_f": L.norm_params(cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.he(kf, (cfg.d_model, cfg.padded_vocab), 1.0, dt)
    return p


def _stack(spec_tree):
    return jax.tree.map(lambda spec: P(*((None,) + tuple(spec))), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def param_specs(cfg):
    s = {
        # embed sharded on d_model (clean gather); head stays vocab-parallel
        "embed": P(None, L.FSDP),
        "blocks": _stack(block_specs(cfg)),
        "ln_f": L.norm_specs(cfg),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = P(L.FSDP, L.TP)  # vocab-parallel logits
    return s


# ---------------------------------------------------------------------------
# Layer stacking
# ---------------------------------------------------------------------------

def _fresh_state(cfg, batch):
    """Zero recurrent state used inside a training step (rwkv/hybrid)."""
    if cfg.family == "rwkv":
        return R.init_layer_state(cfg, batch)
    if cfg.family == "hybrid":
        return {"mamba": S.init_mamba_state(cfg, batch)}
    return None


def _scan_blocks(params, x, cfg, *, positions, states=None, cache_index=None,
                 kv_len_valid=None, ring=False):
    need_state = cfg.family in ("rwkv", "hybrid")
    if states is None and need_state:
        per_layer = _fresh_state(cfg, x.shape[0])
        states = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape),
            per_layer)

    def body(carry, layer_in):
        bp, st = layer_in
        carry = ctx.shard_activations(carry)
        y, new_state = apply_block(bp, carry, cfg, st, positions=positions,
                                   cache_index=cache_index,
                                   kv_len_valid=kv_len_valid, ring=ring)
        return ctx.shard_activations(y), new_state

    f = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        x, new_states = jax.lax.scan(f, x, (params["blocks"], states))
        return x, new_states
    outs = []
    for i in range(cfg.n_layers):
        bp = jax.tree.map(lambda a, i=i: a[i], params["blocks"])
        st = None if states is None else jax.tree.map(
            lambda a, i=i: a[i], states)
        x, ns = f(x, (bp, st))
        outs.append(ns)
    if outs[0] is None:
        return x, None
    return x, jax.tree.map(lambda *xs: jnp.stack(xs), *outs)


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def _head(params, x, cfg):
    head = params.get("lm_head")
    if head is None:
        # tied embeddings: contract on the table's LAST axis (no explicit
        # .T so stored-integer tables route through L.linear untransposed;
        # per-channel exponents on the contraction axis fall back to the
        # float-view path inside linear — the documented tied-head case)
        logits = L.linear(x, params["embed"], "...d,vd->...v", cfg)
    else:
        logits = L.linear(x, head, "...d,dv->...v", cfg)
    if cfg.padded_vocab != cfg.vocab_size:   # mask pad ids
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits


def forward(params, tokens, cfg, *, positions=None):
    """tokens [B,S] -> logits [B,S,V] (teacher-forced / no cache)."""
    b, s = tokens.shape
    x = L.embed_rows(params["embed"], tokens,
                     gather=ctx.embed_lookup).astype(jnp.dtype(cfg.dtype))
    x = ctx.shard_activations(x)
    positions = jnp.arange(s) if positions is None else positions
    x, _ = _scan_blocks(params, x, cfg, positions=positions)
    x = L.apply_norm(params["ln_f"], x, cfg)
    return ctx.shard_logits(_head(params, x, cfg))


def loss_fn(params, batch, cfg):
    """Next-token cross-entropy, f32 logsumexp, mean over tokens."""
    logits = forward(params, batch["tokens"], cfg).astype(jnp.float32)
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# Decode state machines
# ---------------------------------------------------------------------------

def init_decode_state(cfg, batch, max_len):
    idx = jnp.zeros((), jnp.int32)
    if cfg.family in ("dense", "moe"):
        per = L.init_kv_cache(cfg, batch, max_len)
    elif cfg.family == "rwkv":
        per = R.init_layer_state(cfg, batch)
    elif cfg.family == "hybrid":
        per = {"mamba": S.init_mamba_state(cfg, batch),
               "kv": L.init_kv_cache(cfg, batch,
                                     min(max_len, cfg.sliding_window))}
    else:
        raise ValueError(cfg.family)
    layers = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), per)
    return {"layers": layers, "index": idx}


def decode_state_specs(cfg, dp=("data",), tp_size=16):
    if cfg.family in ("dense", "moe"):
        per = L.kv_cache_specs(cfg, dp, tp_size)
    elif cfg.family == "rwkv":
        per = R.state_specs(cfg, dp)
    elif cfg.family == "hybrid":
        per = {"mamba": S.mamba_state_specs(cfg, dp),
               "kv": L.kv_cache_specs(cfg, dp, tp_size)}
    else:
        raise ValueError(cfg.family)
    return {"layers": _stack(per), "index": P()}


def prefill(params, tokens, cfg, state):
    """Prompt pass filling the decode state; returns (last_logits, state).

    dense/moe: writes the whole prompt into the KV cache.
    rwkv:      runs the recurrence, final state is the cache.
    hybrid:    runs banded attention + SSM; the serve driver chunks prompts
               through the cached path W tokens at a time (ring cache), so
               this entry handles prompt_len <= sliding_window directly.
    """
    b, s = tokens.shape
    x = L.embed_rows(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    idx = state["index"]
    per_lane = getattr(idx, "ndim", 0) == 1      # [B] vector (repro.cell)
    if cfg.family in ("dense", "moe"):
        if per_lane:
            # continuous-batching decode: every lane sits at its own depth.
            # Cache writes scatter at [lane, idx[lane]]; positions and the
            # validity bound are per-lane (layers._sdpa_block broadcasts).
            assert s == 1, "per-lane decode state advances one token at " \
                "a time; joins prefill each lane alone (join_lanes)"
            positions = idx[:, None] + jnp.arange(s)
        else:
            positions = idx + jnp.arange(s)
        x, new_layers = _scan_blocks(params, x, cfg, positions=positions,
                                     states=state["layers"], cache_index=idx,
                                     kv_len_valid=idx + s)
    elif cfg.family == "rwkv":
        x, new_layers = _scan_blocks(params, x, cfg, positions=None,
                                     states=state["layers"])
    else:  # hybrid
        assert not per_lane, \
            "per-lane decode indices cover dense/moe/rwkv; hybrid ring " \
            "caches keep the shared-cursor path"
        w = cfg.sliding_window
        positions = idx + jnp.arange(s)
        if s > w:
            # long prompt: banded attention, no cache fill (the serve
            # driver chunks real prompts through the ring path W at a time)
            st = {"mamba": state["layers"]["mamba"]}
            x, nl = _scan_blocks(params, x, cfg, positions=positions,
                                 states=st)
            new_layers = {"mamba": nl["mamba"], "kv": state["layers"]["kv"]}
        else:
            # s == 1: true ring decode (slots may be rotated -> positional
            # causality meaningless; validity mask only).  s > 1: prompt
            # chunk with monotone slots (serve driver aligns chunks so
            # idx + s <= W) -> ordinary causal masking applies.
            x, new_layers = _scan_blocks(
                params, x, cfg, positions=positions, states=state["layers"],
                cache_index=jnp.mod(idx, w),
                kv_len_valid=jnp.minimum(idx + s, w), ring=(s == 1))
    x = L.apply_norm(params["ln_f"], x, cfg)
    logits = _head(params, x[:, -1], cfg)
    return logits, {"layers": new_layers, "index": idx + s}


def decode_step(params, token, cfg, state):
    """One new token [B] against the running state -> (logits [B,V], state).

    ``state["index"]`` may be the usual shared scalar, or a per-lane [B]
    vector (the ``repro.cell`` continuous-batching path: lanes decode at
    heterogeneous depths, cache writes scatter per lane)."""
    return prefill(params, token[:, None], cfg, state)


def merge_decode_state(old, new, lane):
    """Write the prefilled one-lane state ``new`` into ``old`` at ``lane``.

    The join half of continuous batching (cell.scheduler): every
    ``layers`` leaf of ``new`` is stacked ``[n_layers, 1, ...]`` and no
    longer than ``old``'s along any axis; it lands on ``lane`` from the
    start of every later axis, so a cache of ``w`` slots fills slots
    ``0..w`` and later slots keep what they held.  Other lanes are
    untouched: resident lanes never stop decoding.  ``old``'s ``index``
    is the per-lane [B] vector; ``lane``'s takes ``new``'s index.
    """
    def put(o, n):
        return jax.lax.dynamic_update_slice(
            o, n, (0, lane) + (0,) * (o.ndim - 2))

    return {"layers": jax.tree.map(put, old["layers"], new["layers"]),
            "index": old["index"].at[lane].set(new["index"])}


def join_lanes(params, tokens, lanes, n, cfg, state):
    """Prefill the first ``n`` rows of ``tokens`` [slots, w] into lanes
    ``lanes[:n]`` of the live per-lane decode ``state``.

    Each row is prefilled alone, at batch 1 into a fresh ``w``-slot
    state, so attention spans ``w`` keys and no pad row is computed; it
    is then written into its lane through ``merge_decode_state`` (looked
    up at trace time).  The live state is the loop's carry: a caller
    that donates it has it updated in place.  Shapes depend on ``w``
    alone (``n`` is an array), so one program serves every joiner count.
    A joiner's routing (moe) is its own, never shared with co-joiners.
    """
    w = tokens.shape[1]

    def body(i, st):
        row = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=0)
        _, fresh = prefill(params, row, cfg, init_decode_state(cfg, 1, w))
        return merge_decode_state(st, fresh, lanes[i])

    return jax.lax.fori_loop(0, n, body, state)


def forward_no_blocks(params, tokens, cfg):
    """Embed -> final norm -> head only (dry-run cost decomposition)."""
    x = L.embed_rows(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    x = L.apply_norm(params["ln_f"], x, cfg)
    return _head(params, x, cfg)
