"""Shared jaxpr-walking utilities for the static-analysis passes.

``jax.make_jaxpr`` gives the pass pipeline one canonical view of a jitted
program: a list of equations over typed variables, with call-like
primitives (``jit``, ``custom_vjp_call_jaxpr``, ``scan``, ``cond``,
``pallas_call``, ...) carrying nested jaxprs in their params.  The three
helpers here are the only places that touch jax internals:

  ``sub_jaxprs(eqn)``   - every nested Jaxpr inside an equation's params;
  ``iter_eqns(jaxpr)``  - depth-first traversal over all equations;
  ``user_site(eqn)``    - the repo-level (function, file, line) frames an
                          equation was traced from, for whitelists and
                          human-readable reports.
"""

from __future__ import annotations

from jax._src import source_info_util  # noqa: PLC2701  (no public API yet)


def closed_to_open(j):
    """Return the open Jaxpr of a (possibly Closed) jaxpr object."""
    inner = getattr(j, "jaxpr", None)
    return inner if inner is not None and hasattr(inner, "eqns") else j


def sub_jaxprs(eqn):
    """Yield every nested (open) Jaxpr referenced by an equation's params."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if hasattr(v, "jaxpr") or (hasattr(v, "eqns") and
                                       hasattr(v, "invars")):
                yield closed_to_open(v)


def iter_eqns(jaxpr, depth: int = 0):
    """Depth-first (eqn, depth) traversal, recursing into nested jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn, depth
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub, depth + 1)


def user_frames(eqn):
    """Repo-level stack frames (innermost first) for an equation."""
    return list(source_info_util.user_frames(eqn.source_info.traceback))


def frame_functions(eqn) -> list:
    """Function names of the user frames (innermost first)."""
    return [f.function_name for f in user_frames(eqn)]


def user_site(eqn) -> str:
    """Human-readable innermost repo frame: ``fn (file.py:line)``."""
    frames = user_frames(eqn)
    if not frames:
        return ""
    f = frames[0]
    fname = f.file_name.rsplit("/", 1)[-1]
    return f"{f.function_name} ({fname}:{f.start_line})"


def aval_bytes(aval) -> int:
    """Buffer bytes of an abstract value (bools count one byte)."""
    try:
        return int(aval.size) * max(int(aval.dtype.itemsize), 1)
    except Exception:
        return 0
