"""Plain float32 ``jax.numpy`` forwards: the references the served plans
are checked against.

Written from the paper's equations and sharing no code with
``models/layers.py``: exact softmax and GELU, float weights, no LUTs, no
quantisation, no kernels.  Run them under
``jax.default_matmul_precision("highest")`` so the reference is not
itself rounded to bfloat16 on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _layernorm(x, p, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def _dense(x, p, w, b):
    y = x @ _f32(p[w])
    return y + _f32(p[b]) if b in p else y


def kwt_forward(params, mfcc, cfg):
    """KWT (paper §II, eqs 1-8): mfcc [B, F, T] -> logits [B, n_classes].

    Per-time-step patches, linear projection, class token, learned
    positions, post-norm blocks of full multi-head attention and a GELU
    MLP, class-token head.
    """
    x = jnp.swapaxes(_f32(mfcc), 1, 2) @ _f32(params["proj_w"]) \
        + _f32(params["proj_b"])
    b = x.shape[0]
    cls = jnp.broadcast_to(_f32(params["cls"]), (b, 1, cfg.d_model))
    x = jnp.concatenate([cls, x], axis=1) + _f32(params["pos"])
    s = x.shape[1]
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    for bp in params["blocks"]:
        a = bp["attn"]
        q = _dense(x, a, "wq", "bq").reshape(b, s, h, dh)
        k = _dense(x, a, "wk", "bk").reshape(b, s, kv, dh)
        v = _dense(x, a, "wv", "bv").reshape(b, s, kv, dh)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        att = _dense(att.reshape(b, s, h * dh), a, "wo", "bo")
        x = _layernorm(x + att, bp["ln1"])
        m = bp["mlp"]
        f = _dense(jax.nn.gelu(_dense(x, m, "w1", "b1"), approximate=False),
                   m, "w2", "b2")
        x = _layernorm(x + f, bp["ln2"])
    return x[:, 0] @ _f32(params["head_w"]) + _f32(params["head_b"])
