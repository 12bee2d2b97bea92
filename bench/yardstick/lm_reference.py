"""What the plain LM references share, in ``jax.numpy`` and float32.

An architecture module (``bench/arch/<name>.py``) writes its model's
forward pass from the published description, sharing no code with the
program, out of the pieces here: the stated numerics (:class:`Numerics`),
weights read at them (:func:`as_weights`), rounding to the activations'
type (:func:`round_to`), RMSNorm and the rotary rotation.
:func:`teacher_forced` turns that forward pass into the ``evaluate`` and
``logits`` the harness calls.

A reference is teacher-forced over whole sequences, with no cache and no
batching of unequal requests: each row is one request's prompt and the
tokens served to it, padded on the right (the causal mask keeps the
padding out of every real position).  Matmuls run under
``jax.default_matmul_precision("highest")``, so on a TPU too each product
is exact and each sum float32.

The control (:meth:`Numerics.lowered`) puts every weight matrix one
precision step below what is stated: float8 e4m3 with one scale per
matrix and layer, the step a faster plan would take.  It needs nothing of
the architecture, so it holds for every module.
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


def round_to(x, dtype: str):
    return x.astype(jnp.dtype(dtype)).astype(jnp.float32)


def as_weights(w, num: Numerics, stated: str):
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


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rotate_half(x, cos, sin):
    """x [N, S, H, D]; cos, sin [S, D / 2]: the two halves of each head's
    vector rotated against each other."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _frozen(model: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def teacher_forced(forward):
    """``(evaluate, logits)`` of an architecture whose ``forward(w,
    tokens, model, num, stated)`` gives every logit [N, S, V] of
    teacher-forced ``tokens`` [N, S] at the numerics ``num``; ``stated``
    is the weight type the configuration states."""

    def _forward(w, tokens, model, num, stated):
        with jax.default_matmul_precision("highest"):
            return forward(w, tokens, dict(model), num, stated)

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

    def evaluate(w, tokens: np.ndarray, targets: np.ndarray, model: dict,
                 num: Numerics, stated: str):
        """Teacher-forced ``tokens`` [N, S] -> (best logit [N, S], the
        token that has it [N, S], the logits of ``targets`` [T, N, S]), as
        numpy."""
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

    return evaluate, logits
