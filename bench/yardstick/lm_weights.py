"""Weights for an LM, made from the seed on the device in one jitted call,
in the program's parameter layout and the stated dtypes.

The layout is the architecture module's (``shapes(model)`` of
``bench/arch/<name>.py``): ``{path: (shape, role)}``, leaves drawn in
that order, each from a key of its own.  By role:

* ``matrix``: the stated weight type, normal with the standard deviation
  the configuration states (``init_std``: the published
  ``initializer_range``);
* ``scale``: a norm scale in the stated norm type, drawn around 1 as
  ``1 + 0.1 N(0, 1)`` rather than left at 1, so the comparison sees the
  path that carries it;
* ``float32``: a matrix the program keeps in float32 whatever the stated
  weight type (a router, say), normal with ``init_std``.

Layers are stacked on a leading axis, as the program scans them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROLES = ("matrix", "scale", "float32")


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def make(seed: int, leaves: dict, numerics: dict, init_std: float):
    """The weights of the layout ``leaves`` (``{path: (shape, role)}``)
    on the default device, the same for the same seed."""
    key32 = int(np.random.default_rng([int(seed) % 2 ** 64, 13])
                .integers(0, 2 ** 31 - 1))
    std = float(init_std)
    dtypes = {"matrix": jnp.dtype(numerics["weights"]),
              "float32": jnp.dtype(jnp.float32)}
    norm_dt = jnp.dtype(numerics["norm_scales"])
    for path, (_, role) in leaves.items():
        if role not in ROLES:
            raise ValueError(f"{path}: role {role!r} is none of {ROLES}")

    def init(key):
        keys = dict(zip(leaves, jax.random.split(key, len(leaves))))
        flat = {}
        for path, (shape, role) in leaves.items():
            if role == "scale":
                flat[path] = (1.0 + 0.1 * jax.random.normal(
                    keys[path], shape, jnp.float32)).astype(norm_dt)
            else:
                flat[path] = (std * jax.random.normal(
                    keys[path], shape, jnp.float32)).astype(dtypes[role])
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
