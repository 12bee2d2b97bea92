"""Pallas geometry pass: validate kernel block shapes before launch.

Every ``pallas_call`` in the plan's forward program is checked statically
— at plan time, not when the kernel first faults on device:

  * **VMEM fit**: the per-step working set (block bytes over all operand
    and output BlockSpecs, doubled for the pipeline's double-buffering)
    must fit in a core's ~16 MB of VMEM.
  * **Mosaic tiling**: compiled (non-interpret) plans want the last axis
    a multiple of 128 lanes and the second-to-last a multiple of 8
    sublanes (float32 tiling); interpret-mode plans get the same note as
    a warning, since flipping ``kernel_interpret`` is how these plans
    reach real hardware.
  * **Grid consistency**: a zero/negative grid axis or a block larger
    than its (padded) array means ``pad_to_block``/``fit_block`` chose
    an impossible geometry.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.analysis import jaxpr_walk as jw
from repro.analysis.report import Finding, PassResult

VMEM_BYTES = 16 * 1024 * 1024      # per-core VMEM (pallas guide)
_LANE, _SUBLANE = 128, 8           # float32 Mosaic tile


def _block_dims(bm) -> tuple:
    """Block edge sizes (``Blocked`` block dims carry a ``block_size``;
    squeezed dims are ``None`` and drop out)."""
    return tuple(int(getattr(d, "block_size", d)) for d in bm.block_shape
                 if d is not None)


def _block_bytes(bm) -> int:
    dtype = bm.array_aval.dtype
    return int(math.prod(_block_dims(bm))) * int(jnp.dtype(dtype).itemsize)


def _kernel_name(eqn) -> str:
    info = eqn.params.get("name_and_src_info")
    name = getattr(info, "name", None) or str(info or "pallas_call")
    return name.split(" ")[0]


def check_geometry(engine, x) -> PassResult:
    """Walk the forward jaxpr and vet every pallas_call's geometry."""
    findings = []
    metrics = {"kernels": 0, "max_vmem_bytes": 0}
    cfg = engine.exec_cfg
    closed = jax.make_jaxpr(
        lambda p, xx: engine._mod.forward(p, xx, cfg))(engine.params, x)

    for eqn, _ in jw.iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        metrics["kernels"] += 1
        name = _kernel_name(eqn)
        gm = eqn.params["grid_mapping"]
        grid = tuple(int(g) for g in gm.grid)
        interpret = bool(eqn.params.get("interpret", False))

        if any(g <= 0 for g in grid):
            findings.append(Finding(
                "violation", "empty-grid",
                f"{name}: grid {grid} has a non-positive axis",
                jw.user_site(eqn)))
            continue

        vmem = 0
        for bm in gm.block_mappings:
            vmem += _block_bytes(bm)
            block = _block_dims(bm)
            arr = tuple(int(d) for d in bm.array_aval.shape)
            if len(block) == len(arr) and any(
                    b > max(a, 1) and b % max(a, 1) != 0
                    for b, a in zip(block, arr)):
                findings.append(Finding(
                    "violation", "block-overhang",
                    f"{name}: block {block} is not a tile of array "
                    f"{arr} (pad_to_block/fit_block mismatch)",
                    jw.user_site(eqn)))
            # Mosaic takes a block edge that is a tile multiple or the
            # whole array edge
            if len(block) == len(arr) and (
                    len(block) >= 1 and block[-1] % _LANE
                    and block[-1] != arr[-1] or
                    len(block) >= 2 and block[-2] % _SUBLANE
                    and block[-2] != arr[-2]):
                findings.append(Finding(
                    "warning", "mosaic-tile",
                    f"{name}: block {block} is not {_SUBLANE}x{_LANE}-"
                    "aligned — fine in interpret mode"
                    + ("" if interpret else
                       "; Mosaic will pad or reject it"),
                    jw.user_site(eqn)))

        working = 2 * vmem            # double-buffered pipeline
        metrics["max_vmem_bytes"] = max(metrics["max_vmem_bytes"], working)
        if working > VMEM_BYTES:
            findings.append(Finding(
                "violation", "vmem-overflow",
                f"{name}: per-step working set {working} B "
                f"(2x double-buffer) exceeds VMEM {VMEM_BYTES} B; "
                "shrink the block via fit_block", jw.user_site(eqn)))
        else:
            findings.append(Finding(
                "info", "kernel-geometry",
                f"{name}: grid {grid}, working set {working} B "
                f"of {VMEM_BYTES} B VMEM "
                f"({'interpret' if interpret else 'mosaic'})"))

    if metrics["kernels"] == 0:
        findings.append(Finding(
            "info", "scope",
            f"plan {engine.backend_name!r} launches no Pallas kernels"))
    return PassResult("geometry", findings, metrics)
