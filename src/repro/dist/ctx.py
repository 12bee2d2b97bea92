"""Mesh/sharding context: the model code's "where am I running".

Model code (transformer / encdec / moe) is written mesh-agnostically: it
calls the helpers below at every activation boundary and they resolve, at
trace time, to either a no-op (single device, no mesh — the KWT/CPU test
path) or a ``NamedSharding`` constraint on the ambient mesh when inside

    with ctx.mesh_context(mesh, dp_axes, seq_axis=...):
        ...

which makes ``mesh`` the ambient mesh (``jax.set_mesh``) and declares
the axes; :func:`current_mesh` reads it back.

Axis conventions (launch/mesh.py, DESIGN.md §3):
  'pod', 'data'  — data-parallel / FSDP axes (``dp_axes``),
  'model'        — tensor-parallel axis; when ``seq_axis='model'`` the
                   activations additionally shard their SEQUENCE dim over
                   it between blocks (Megatron-SP), gathered just-in-time
                   by ``unshard_seq`` before attention/MLP.

Axis names not present on the ambient mesh are dropped from every
constraint, so the same model code runs on (data,), (data, model) and
(pod, data, model) meshes unchanged.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

TP = "model"   # tensor-parallel axis name (layers.TP; kept free of imports)


class _State(threading.local):
    active = False
    dp = None
    seq_axis = None


_STATE = _State()


@contextlib.contextmanager
def mesh_context(mesh, dp_axes, seq_axis=None):
    """Enter ``mesh`` and declare the data-parallel axes (and optional
    Megatron-SP sequence axis) that activation constraints shard over.
    ``dp_axes`` may be None/() for replicated-batch cells (e.g.
    long-context batch 1).  Contexts nest; the outer declaration is
    restored on exit."""
    prev = (_STATE.active, _STATE.dp, _STATE.seq_axis)
    _STATE.active = True
    _STATE.dp = tuple(dp_axes) if dp_axes else None
    _STATE.seq_axis = seq_axis
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _STATE.active, _STATE.dp, _STATE.seq_axis = prev


def current_mesh():
    """The ambient mesh (empty outside :func:`mesh_context`)."""
    return jax.sharding.get_abstract_mesh()


def _mesh_active() -> bool:
    """True only under ``mesh_context`` AND a real (entered) device mesh."""
    return _STATE.active and not current_mesh().empty


def dp_axes():
    """The data-parallel axes declared by the enclosing ``mesh_context``."""
    return _STATE.dp


def _present(axis, mesh):
    """Drop axis names the ambient mesh doesn't have."""
    if axis is None:
        return None
    if isinstance(axis, (tuple, list)):
        kept = tuple(a for a in axis if a in mesh.axis_names)
        return kept or None
    return axis if axis in mesh.axis_names else None


def _constrain(x, dims):
    mesh = current_mesh()
    spec = P(*(_present(d, mesh) for d in dims))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_activations(x):
    """[B, S, D] activations: batch over the DP axes, sequence over the
    Megatron-SP axis when one was declared.  No-op off-mesh."""
    if not _mesh_active():
        return x
    return _constrain(x, (_STATE.dp, _STATE.seq_axis, None))


def unshard_seq(x):
    """Gather Megatron-SP sequence shards (attention/MLP need the full
    sequence); no-op unless a ``seq_axis`` was declared."""
    if not _mesh_active() or _STATE.seq_axis is None:
        return x
    return _constrain(x, (_STATE.dp, None, None))


def shard_logits(x):
    """[B, S, V] logits: batch over DP, vocab over TP (the lm_head is
    vocab-parallel, P(FSDP, TP) — keep its product sharded the same way
    instead of letting GSPMD replicate [B, S, V])."""
    if not _mesh_active():
        return x
    return _constrain(x, (_STATE.dp, None, TP))


def embed_lookup(embed, tokens):
    """Token-embedding gather.  On-mesh the result is pinned straight to
    the DP activation layout so GSPMD gathers from the d_model-sharded
    table in place rather than replicating the table through the take."""
    x = jnp.take(embed, tokens, axis=0)
    if _mesh_active():
        x = _constrain(x, (_STATE.dp, None, None))
    return x
