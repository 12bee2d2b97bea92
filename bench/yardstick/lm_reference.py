"""Plain reference of a dense decoder LM, in ``jax.numpy`` and float32.

Written from the published description (InternLM2, arXiv:2403.17297,
section 2; the Llama-style block it follows) and sharing no code with
the program: token embedding; per layer a pre-RMSNorm, grouped-query
attention with rotary positions (the two halves of each head's vector
rotated against each other, frequency ``theta ** (-i / (head_dim / 2))``)
and a causal mask, a residual add, a pre-RMSNorm, a SwiGLU MLP
(``silu(x W_gate) * (x W_up)``, then ``W_down``) and a residual add; a
final RMSNorm and an untied head.

It is teacher-forced over whole sequences, with no cache and no batching
of unequal requests: each row is one request's prompt and the tokens
served to it, padded on the right (the causal mask keeps the padding
out of every real position).  Matmuls run under
``jax.default_matmul_precision("highest")``, so on a TPU too each product
is exact and each sum float32.

At the numerics a configuration states (:class:`Numerics`) the activations
are rounded to ``activations`` where the configuration says the program
holds them in that type: after each norm, each projection, the rotation,
the attention-weighted values, each residual add and the gated product;
the attention probabilities are rounded to it before they weigh the
values.  Scores, softmax, norms, the rotation and the SiLU are float32.
The head's logits stay float32: they are the yardstick.

The control (:meth:`Numerics.lowered`) puts every weight matrix one
precision step below what is stated: float8 e4m3 with one scale per
matrix and layer, the step a faster plan would take.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Numerics:
    activations: str            # a dtype name, e.g. ``bfloat16``
    weights: str                # the stated weight type, or a lower one
    rms_norm_eps: float

    @classmethod
    def stated(cls, num: dict) -> "Numerics":
        return cls(activations=num["activations"], weights=num["weights"],
                   rms_norm_eps=float(num["rms_norm_eps"]))

    def lowered(self) -> "Numerics":
        """Weight matrices one step below the stated type."""
        return dataclasses.replace(self, weights=LOWER[self.weights])


LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def _round(x, dtype: str):
    return x.astype(jnp.dtype(dtype)).astype(jnp.float32)


def _as_weights(w, num: Numerics, stated: str):
    """A weight matrix [..., rows, cols] in float32 at ``num.weights``:
    as stored where that is the stated type; rounded to a lower type that
    has float32's range; or, to an 8-bit float, scaled so that its
    largest entry meets the type's largest, rounded, scaled back."""
    w = w.astype(jnp.float32)
    if num.weights == stated:
        return w
    dt = jnp.dtype(num.weights)
    if jnp.finfo(dt).bits > 8:
        return w.astype(dt).astype(jnp.float32)
    big = float(jnp.finfo(dt).max)
    amax = jnp.max(jnp.abs(w), axis=(-2, -1), keepdims=True)
    scale = jnp.where(amax > 0, amax / big, 1.0)
    return (w / scale).astype(dt).astype(jnp.float32) * scale


def rope_tables(length: int, head_dim: int, theta: float):
    """cos and sin [length, head_dim / 2], angles in float64."""
    half = head_dim // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rotate(x, cos, sin):
    """x [N, S, H, D]; cos, sin [S, D / 2]."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _hidden(w, tokens, model: dict, num: Numerics, stated: str):
    """Final normed hidden states [N, S, d] of teacher-forced ``tokens``."""
    act = functools.partial(_round, dtype=num.activations)
    n, s = tokens.shape
    h, kv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps = num.rms_norm_eps
    cos, sin = rope_tables(s, dh, float(model["rope_theta"]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    x = act(_as_weights(w["embed"], num, stated)[tokens])

    def layer(x, blk):
        a, m = blk["attn"], blk["mlp"]
        y = act(_rms(x, blk["ln1"]["scale"], eps))
        q = act(y @ _as_weights(a["wq"], num, stated)).reshape(n, s, h, dh)
        k = act(y @ _as_weights(a["wk"], num, stated)).reshape(n, s, kv, dh)
        v = act(y @ _as_weights(a["wv"], num, stated)).reshape(n, s, kv, dh)
        q, k = act(_rotate(q, cos, sin)), act(_rotate(k, cos, sin))
        q = q.reshape(n, s, kv, h // kv, dh)
        scores = jnp.einsum("nqkgd,nskd->nkgqs", q, k) * dh ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        p = act(jax.nn.softmax(scores, axis=-1))
        o = act(jnp.einsum("nkgqs,nskd->nqkgd", p, v)).reshape(n, s, h * dh)
        x = act(x + act(o @ _as_weights(a["wo"], num, stated)))
        y = act(_rms(x, blk["ln2"]["scale"], eps))
        gate = jax.nn.silu(act(y @ _as_weights(m["w_gate"], num, stated)))
        up = act(y @ _as_weights(m["w_up"], num, stated))
        x = act(x + act(act(gate * up) @ _as_weights(m["w_down"], num,
                                                      stated)))
        return x, None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    return act(_rms(x, w["ln_f"]["scale"], eps))


def _forward(w, tokens, model, num, stated):
    with jax.default_matmul_precision("highest"):
        x = _hidden(w, tokens, dict(model), num, stated)
        return x @ _as_weights(w["lm_head"], num, stated)


_logits = jax.jit(_forward, static_argnames=("model", "num", "stated"))


@functools.partial(jax.jit, static_argnames=("model", "num", "stated"))
def _evaluate(w, tokens, targets, model, num, stated):
    logits = _forward(w, tokens, model, num, stated)
    best = jnp.max(logits, axis=-1)
    top = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    picked = jnp.stack([jnp.take_along_axis(logits, t[..., None],
                                            axis=-1)[..., 0]
                        for t in targets])
    return best, top, picked


def _frozen(model: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def evaluate(w, tokens: np.ndarray, targets: np.ndarray, model: dict,
             num: Numerics, stated: str):
    """Teacher-forced ``tokens`` [N, S] -> (best logit [N, S], the token
    that has it [N, S], the logits of ``targets`` [T, N, S]), as numpy.
    ``stated`` is the weight type the configuration states."""
    out = _evaluate(w, jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(targets, jnp.int32), _frozen(model), num,
                    stated)
    return tuple(np.asarray(a) for a in out)


def logits(w, tokens: np.ndarray, model: dict, num: Numerics,
           stated: str) -> np.ndarray:
    """Every logit [N, S, V] of teacher-forced ``tokens``: for small
    models (the tests)."""
    return np.asarray(_logits(w, jnp.asarray(tokens, jnp.int32),
                              _frozen(model), num, stated))
