"""Weights for a dense decoder LM, made from the seed on the device in one
jitted call, in the program's parameter layout and the stated dtypes.

Every matrix (the embedding, the attention and SwiGLU projections, the
untied head) is normal with the standard deviation the configuration
states (``init_std``: the published ``initializer_range``); the RMSNorm
scales are drawn around 1 rather than left at 1, so the comparison sees
the path that carries them.  Layers are stacked on a leading axis, as the
program scans them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

def shapes(model: dict) -> dict:
    """``{path: shape}`` of every leaf, layers stacked first."""
    n, d, ff, v = (model["n_layers"], model["d_model"], model["d_ff"],
                   model["vocab_size"])
    q, kv = (model["n_heads"] * model["head_dim"],
             model["n_kv_heads"] * model["head_dim"])
    return {
        "embed": (v, d), "lm_head": (d, v), "ln_f/scale": (d,),
        "blocks/ln1/scale": (n, d), "blocks/ln2/scale": (n, d),
        "blocks/attn/wq": (n, d, q), "blocks/attn/wk": (n, d, kv),
        "blocks/attn/wv": (n, d, kv), "blocks/attn/wo": (n, q, d),
        "blocks/mlp/w_gate": (n, d, ff), "blocks/mlp/w_up": (n, d, ff),
        "blocks/mlp/w_down": (n, ff, d),
    }


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def make(seed: int, model: dict, numerics: dict, init_std: float):
    """The weights on the default device, the same for the same seed:
    matrices of standard deviation ``init_std`` in
    ``numerics['weights']``, norm scales in ``numerics['norm_scales']``."""
    key32 = int(np.random.default_rng([int(seed) % 2 ** 64, 13])
                .integers(0, 2 ** 31 - 1))
    std = float(init_std)
    mat_dt = jnp.dtype(numerics["weights"])
    norm_dt = jnp.dtype(numerics["norm_scales"])
    leaves = shapes(model)

    def init(key):
        keys = dict(zip(leaves, jax.random.split(key, len(leaves))))
        flat = {}
        for path, shape in leaves.items():
            if path.endswith("scale"):
                flat[path] = (1.0 + 0.1 * jax.random.normal(
                    keys[path], shape, jnp.float32)).astype(norm_dt)
            else:
                flat[path] = (std * jax.random.normal(
                    keys[path], shape, jnp.float32)).astype(mat_dt)
        return _nest(flat)

    return jax.jit(init)(jax.random.PRNGKey(key32))


def check_layout(params, program_params) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of the
    program's own (``program_params``, shapes only)."""
    mine = jax.tree_util.tree_structure(params)
    theirs = jax.tree_util.tree_structure(program_params)
    if mine != theirs:
        raise ValueError(f"weights tree {mine} is not the program's "
                         f"{theirs}")
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(program_params)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path)}: {a.shape} "
                             f"{a.dtype} here, {b.shape} {b.dtype} in the "
                             "program")
