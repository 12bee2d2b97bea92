"""A dense decoder LM: grouped-query attention with rotary positions and
a SwiGLU MLP in every layer (InternLM2, arXiv:2403.17297, section 2; the
Llama-style block it follows).

An LM configuration names its architecture (``"architecture": "dense"``)
and the harness finds this module by that name.  It gives the weights'
layout (:func:`shapes`), the plain reference (:func:`evaluate`,
:func:`logits`) and the work counted from shapes (:func:`tokens_flops`,
:func:`decode_min_bytes`).

The reference is written from the published description and shares no
code with the program: token embedding; per layer a pre-RMSNorm,
grouped-query attention with rotary positions (the two halves of each
head's vector rotated against each other, frequency
``theta ** (-i / (head_dim / 2))``) and a causal mask, a residual add, a
pre-RMSNorm, a SwiGLU MLP (``silu(x W_gate) * (x W_up)``, then
``W_down``) and a residual add; a final RMSNorm and an untied head.

At the numerics a configuration states the activations are rounded to
``activations`` where the configuration says the program holds them in
that type: after each norm, each projection, the rotation, the
attention-weighted values, each residual add and the gated product; the
attention probabilities are rounded to it before they weigh the values.
Scores, softmax, norms, the rotation and the SiLU are float32.  The
head's logits stay float32: they are the yardstick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from yardstick import lm_reference
from yardstick.lm_reference import as_weights, rms_norm, rotate_half, round_to


def shapes(model: dict) -> dict:
    """``{path: (shape, role)}`` of every leaf, layers stacked first, in
    the order the weights are drawn."""
    n, d, ff, v = (model["n_layers"], model["d_model"], model["d_ff"],
                   model["vocab_size"])
    q, kv = (model["n_heads"] * model["head_dim"],
             model["n_kv_heads"] * model["head_dim"])
    m, s = "matrix", "scale"
    return {
        "embed": ((v, d), m), "lm_head": ((d, v), m), "ln_f/scale": ((d,), s),
        "blocks/ln1/scale": ((n, d), s), "blocks/ln2/scale": ((n, d), s),
        "blocks/attn/wq": ((n, d, q), m), "blocks/attn/wk": ((n, d, kv), m),
        "blocks/attn/wv": ((n, d, kv), m), "blocks/attn/wo": ((n, q, d), m),
        "blocks/mlp/w_gate": ((n, d, ff), m),
        "blocks/mlp/w_up": ((n, d, ff), m),
        "blocks/mlp/w_down": ((n, ff, d), m),
    }


# -- the reference ------------------------------------------------------------

def _hidden(w, tokens, model: dict, num, stated: str):
    """Final normed hidden states [N, S, d] of teacher-forced ``tokens``."""
    act = functools.partial(round_to, dtype=num.activations)
    n, s = tokens.shape
    h, kv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps = num.rms_norm_eps
    cos, sin = lm_reference.rope_tables(s, dh, float(model["rope_theta"]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    x = act(as_weights(w["embed"], num, stated)[tokens])

    def layer(x, blk):
        a, m = blk["attn"], blk["mlp"]
        y = act(rms_norm(x, blk["ln1"]["scale"], eps))
        q = act(y @ as_weights(a["wq"], num, stated)).reshape(n, s, h, dh)
        k = act(y @ as_weights(a["wk"], num, stated)).reshape(n, s, kv, dh)
        v = act(y @ as_weights(a["wv"], num, stated)).reshape(n, s, kv, dh)
        q, k = act(rotate_half(q, cos, sin)), act(rotate_half(k, cos, sin))
        q = q.reshape(n, s, kv, h // kv, dh)
        scores = jnp.einsum("nqkgd,nskd->nkgqs", q, k) * dh ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        p = act(jax.nn.softmax(scores, axis=-1))
        o = act(jnp.einsum("nkgqs,nskd->nqkgd", p, v)).reshape(n, s, h * dh)
        x = act(x + act(o @ as_weights(a["wo"], num, stated)))
        y = act(rms_norm(x, blk["ln2"]["scale"], eps))
        gate = jax.nn.silu(act(y @ as_weights(m["w_gate"], num, stated)))
        up = act(y @ as_weights(m["w_up"], num, stated))
        x = act(x + act(act(gate * up) @ as_weights(m["w_down"], num,
                                                    stated)))
        return x, None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    return act(rms_norm(x, w["ln_f"]["scale"], eps))


def forward(w, tokens, model: dict, num, stated: str):
    """Every logit [N, S, V] of teacher-forced ``tokens``."""
    x = _hidden(w, tokens, model, num, stated)
    return x @ as_weights(w["lm_head"], num, stated)


evaluate, logits = lm_reference.teacher_forced(forward)


# -- work, counted from shapes ------------------------------------------------

def _widths(model: dict):
    return (model["d_model"], model["n_heads"] * model["head_dim"],
            model["n_kv_heads"] * model["head_dim"], model["d_ff"])


def layer_weights(model: dict) -> int:
    """Entries of one layer's matrices: Q, K, V, output, SwiGLU gate, up,
    down."""
    d, q, kv, ff = _widths(model)
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def tokens_flops(model: dict, start: int, count: int, head: bool) -> int:
    """Matmul operations of ``count`` consecutive tokens of one sequence
    at positions ``start`` .. ``start + count - 1``, each attending to
    itself and every position before it (scores and weighted values);
    with ``head``, each token's logits too.  Padding and positions past
    the sequence are not work."""
    d, q, _, _ = _widths(model)
    keys = count * start + count * (count + 1) // 2
    per_layer = 2 * count * layer_weights(model) + 2 * 2 * keys * q
    logits = 2 * count * d * model["vocab_size"] if head else 0
    return model["n_layers"] * per_layer + logits


def weight_read_bytes(model: dict, weight_bytes: int, norm_bytes: int) -> int:
    """Bytes of the weights one decode step reads whole: every layer's
    matrices and norms, the final norm and the head (the embedding table
    is read only at the rows looked up)."""
    d = model["d_model"]
    return (model["n_layers"] * layer_weights(model)
            + d * model["vocab_size"]) * weight_bytes \
        + (2 * model["n_layers"] + 1) * d * norm_bytes


def decode_min_bytes(model: dict, depths, act_bytes: int, weight_bytes: int,
                     norm_bytes: int) -> int:
    """Least bytes one decode step moves for lanes at cache positions
    ``depths`` (the position each lane's new token takes): the weights
    once; for each lane its embedding row, the keys and values of its
    valid earlier positions read, its new ones written, and its logits
    out.  Cache slots past a lane's depth, and lanes not serving a
    request, are not counted."""
    d, _, kv, _ = _widths(model)
    kv_token = model["n_layers"] * 2 * kv * act_bytes
    per_lane = kv_token + d * weight_bytes + model["vocab_size"] * act_bytes
    return weight_read_bytes(model, weight_bytes, norm_bytes) \
        + kv_token * int(sum(depths)) + per_lane * len(depths)
