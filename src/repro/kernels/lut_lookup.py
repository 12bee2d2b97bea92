"""In-kernel table lookup in the form the TPU compiler (Mosaic) accepts.

Mosaic lowers a gather only as a per-vreg lane gather: a 2-D
``take_along_axis`` whose source row spans at most one 128-lane vreg.
A ``jnp.take`` on a 1-D table is refused ("Only 2D gather is
supported"), and a gather from a row wider than 128 lanes is refused
("Multiple source vregs along gather dimension").

So the ROM tables enter a kernel as ``[ceil(n/128), 128]`` segments
(:func:`lane_table`), and :func:`lookup` gathers each 128-lane chunk of
the index block from every segment, keeping the segment the index falls
in with a select.  The result is the table entry itself, so a kernel
that looks up through here is bit-identical to ``jnp.take`` on the
1-D table.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

LANES = 128


def lane_table(tab: np.ndarray) -> np.ndarray:
    """1-D table -> ``[ceil(n/128), 128]`` zero-padded lane segments."""
    n_seg = -(-tab.size // LANES)
    return np.pad(tab, (0, n_seg * LANES - tab.size)).reshape(n_seg, LANES)


def lookup(tab: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``tab.ravel()[idx]`` inside a kernel body.

    ``tab`` is a :func:`lane_table` block loaded from VMEM; ``idx`` is an
    int32 ``[R, C]`` block of in-range indices, any ``C``.  A chunk
    narrower than 128 lanes is padded to one vreg for the gather and
    sliced back.
    """
    rows, cols = idx.shape
    n_seg = tab.shape[0]
    chunks = []
    for j in range(0, cols, LANES):
        ij = idx[:, j:j + LANES]
        width = ij.shape[1]
        if width < LANES:
            ij = jnp.pad(ij, ((0, 0), (0, LANES - width)))
        lane = ij & (LANES - 1)
        val = None
        for s in range(n_seg):
            seg = jnp.broadcast_to(tab[s:s + 1], (rows, LANES))
            got = jnp.take_along_axis(seg, lane, axis=1)
            val = got if val is None else jnp.where(ij >= s * LANES, got, val)
        chunks.append(val[:, :width])
    return chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)
