"""Float weights for a KWT configuration, made from the seed on the device
in one jitted call, in the program's parameter layout.

Matrices are He-scaled normals; biases, the class token, positions and the
norms' affine parameters are drawn too (not left at zero or one), so the
comparison sees every path that carries them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _init(key, model: dict):
    f, t = model["input_dim"]
    d, ff, c = model["d_model"], model["d_ff"], model["n_classes"]
    inner = model["n_heads"] * model["head_dim"]
    keys = iter(jax.random.split(key, 8 + 16 * model["n_layers"]))

    def mat(shape):
        return jax.random.normal(next(keys), shape) / np.sqrt(shape[0])

    def vec(n, scale, mean=0.0):
        return mean + scale * jax.random.normal(next(keys), (n,))

    def norm():
        return {"scale": vec(d, 0.1, 1.0), "bias": vec(d, 0.1)}

    blocks = [{"ln1": norm(), "ln2": norm(),
               "attn": {"wq": mat((d, inner)), "wk": mat((d, inner)),
                        "wv": mat((d, inner)), "wo": mat((inner, d)),
                        "bq": vec(inner, 0.02), "bk": vec(inner, 0.02),
                        "bv": vec(inner, 0.02), "bo": vec(d, 0.02)},
               "mlp": {"w1": mat((d, ff)), "w2": mat((ff, d)),
                       "b1": vec(ff, 0.02), "b2": vec(d, 0.02)}}
              for _ in range(model["n_layers"])]
    return {"proj_w": mat((f, d)), "proj_b": vec(d, 0.02),
            "cls": vec(d, 0.02),
            "pos": 0.02 * jax.random.normal(next(keys), (t + 1, d)),
            "blocks": blocks,
            "head_w": mat((d, c)), "head_b": vec(c, 0.02)}


def make(seed: int, model: dict):
    """float32 weights on the default device, the same for the same seed."""
    key32 = int(np.random.default_rng([int(seed) % 2 ** 64, 3])
                .integers(0, 2 ** 31 - 1))
    return jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(jnp.float32), _init(k, model)))(
            jax.random.PRNGKey(key32))


def to_host(params) -> dict:
    """The same weights as float64 numpy, for the reference."""
    return jax.tree.map(lambda a: np.asarray(a, np.float64), params)
