"""Power-of-2 post-training static quantisation (paper §IV, eq 9, Table V).

    W_int = floor(W_float * 2^y), stored INT8, dequantised by bit shift.

Design points carried over from the paper:
  * scale factors are powers of two so (de)quantisation is a shift;
  * weights and inputs get *separate* exponents (Table V: 2^6 vs 2^5);
  * intermediate results of int matmuls accumulate wider (paper: INT16
    residuals; on TPU the MXU gives int32 accumulation for free, and we
    optionally clip back to int16 to reproduce the paper's storage type);
  * SoftMax and LayerNorm stay in float in the faithful path (§IV cites
    [12]: quantising them is "quite taxing on accuracy").

Beyond-paper (flagged, see DESIGN.md §5): per-channel exponents, int8
quantised Adam moments, int8 error-feedback gradient compression — the same
eq-9 primitive applied at other points of the stack.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

INT8_MIN, INT8_MAX = -128, 127
INT16_MIN, INT16_MAX = -(2**15), 2**15 - 1


def int_range(bits: int) -> tuple[int, int]:
    """The two's-complement range of a ``bits``-wide signed integer."""
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def storage_dtype(bits: int):
    """Narrowest container dtype for ``bits``-wide values.

    ``bits<=4`` values are *stored* nibble-packed (two per uint8 byte, see
    :func:`pack_po2`); their element dtype before packing is int8.
    """
    return jnp.int8 if bits <= 8 else jnp.int16


# ---------------------------------------------------------------------------
# The packed-int codec.  ONE implementation shared by Engine weights
# (integer-resident QTensors), QAT export artifacts (qat/export.py),
# compressed gradient payloads (dist/compress.py) and checkpoints.
# ---------------------------------------------------------------------------

def packed_length(n: int, bits: int) -> int:
    """Stored bytes for ``n`` values at ``bits`` width (nibble packing)."""
    return (n + 1) // 2 if bits <= 4 else n


def pack_po2(values: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Pack ``bits<=4`` two's-complement values, two nibbles per byte.

    ``values`` is any int array whose elements fit the ``bits``-wide range;
    the result is a flat uint8 array of ``ceil(n/2)`` bytes (low nibble =
    even index).  Odd lengths pad the final high nibble with zero; empty
    tensors pack to an empty byte string.  Exact inverse: :func:`unpack_po2`
    with the original shape — integers in, integers out, no float detour.
    """
    assert 1 <= bits <= 4, f"pack_po2 is the sub-byte codec (bits={bits})"
    flat = values.reshape(-1).astype(jnp.uint8)        # two's-complement wrap
    if flat.size % 2:
        flat = jnp.concatenate([flat, jnp.zeros((1,), jnp.uint8)])
    pairs = flat.reshape(-1, 2)
    return ((pairs[:, 0] & 0xF) | ((pairs[:, 1] & 0xF) << 4)).astype(jnp.uint8)


def unpack_po2(packed: jnp.ndarray, bits: int, shape) -> jnp.ndarray:
    """Inverse of :func:`pack_po2`: nibble-packed bytes -> int8 ``shape``.

    Sign-extends each 4-bit two's-complement nibble ((v ^ 8) - 8), so the
    round-trip is exact for every value in the ``bits``-wide range.
    """
    assert 1 <= bits <= 4, f"unpack_po2 is the sub-byte codec (bits={bits})"
    n = int(np.prod(shape, dtype=np.int64))
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    flat = jnp.stack([lo, hi], axis=-1).reshape(-1)[:n]
    return ((flat.astype(jnp.int8) ^ 8) - 8).reshape(shape)


def pack_payload(values: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Storage form of an int tensor: nibble-packed for ``bits<=4``, the
    narrowest int dtype otherwise (the codec entry point non-QTensor
    callers — dist/compress payloads, export writers — share)."""
    if bits <= 4:
        return pack_po2(values, bits)
    return values.astype(storage_dtype(bits))


def unpack_payload(payload: jnp.ndarray, bits: int, shape) -> jnp.ndarray:
    """Inverse of :func:`pack_payload` (identity above 4 bits)."""
    if bits <= 4:
        return unpack_po2(payload, bits, shape)
    return payload.reshape(shape)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QTensor:
    """An eq-9 quantised tensor: int values + static power-of-2 exponent.

    Storage is dtype-true (the bytes a 64 kB device would hold): int8 for
    ``4 < bits <= 8``, int16 above, and nibble-packed uint8 (two values
    per byte, :func:`pack_po2`) for ``bits <= 4``.  When packed,
    ``logical_shape`` carries the pre-pack shape and ``values`` is the
    flat byte image; :meth:`int_values` restores the int8 grid (inside
    jit too — unpacking is pure bit arithmetic).
    """

    values: jnp.ndarray               # int8 / int16, or uint8 nibble-packed
    exponent: int = dataclasses.field(metadata=dict(static=True))
    axis_exponents: jnp.ndarray | None = None    # per-channel (beyond-paper)
    bits: int = dataclasses.field(default=8, metadata=dict(static=True))
    logical_shape: tuple | None = dataclasses.field(
        default=None, metadata=dict(static=True))    # set iff nibble-packed

    @classmethod
    def store(cls, q: jnp.ndarray, exponent: int, *, bits: int = 8,
              axis_exponents: jnp.ndarray | None = None) -> "QTensor":
        """Build a dtype-true QTensor from an (already clipped) int grid."""
        qi = q.astype(storage_dtype(bits))     # signed cast BEFORE nibble wrap
        if bits <= 4:
            return cls(values=pack_po2(qi, bits), exponent=exponent,
                       axis_exponents=axis_exponents, bits=bits,
                       logical_shape=tuple(qi.shape))
        return cls(values=qi, exponent=exponent,
                   axis_exponents=axis_exponents, bits=bits)

    @property
    def packed(self) -> bool:
        return self.logical_shape is not None

    @property
    def shape(self):
        return self.logical_shape if self.packed else self.values.shape

    @property
    def stored_bytes(self) -> int:
        """True packed storage bytes (values + per-channel exponents)."""
        b = self.values.size * self.values.dtype.itemsize
        if self.axis_exponents is not None:
            b += self.axis_exponents.size * self.axis_exponents.dtype.itemsize
        return b

    def int_values(self) -> jnp.ndarray:
        """The integer grid at its logical shape (unpacks when packed)."""
        if self.packed:
            return unpack_po2(self.values, self.bits, self.logical_shape)
        return self.values

    def dequantize(self) -> jnp.ndarray:
        scale = jnp.float32(2.0 ** (-self.exponent))
        out = self.int_values().astype(jnp.float32) * scale
        if self.axis_exponents is not None:
            out = out * jnp.exp2(-self.axis_exponents.astype(jnp.float32))
        return out


def quantize_po2(w: jnp.ndarray, exponent: int, *, bits: int = 8,
                 stochastic_key: jax.Array | None = None,
                 rounding: str = "floor") -> QTensor:
    """eq 9: floor(w * 2^y) with saturation to the ``bits``-wide int range.

    ``rounding="nearest"`` adds the half-LSB offset before the floor (an
    adder in front of the truncating shift in hardware terms): floor's
    systematic -LSB/2 bias is correlated across every weight and measurably
    shifts whole-model logits; the offset removes it at zero ROM cost.

    Storage is the narrowest dtype for ``bits`` (int8 up to 8 bits,
    nibble-packed below 5 — no silent int16 widening), and saturation
    clips at the true ``bits``-wide edges (e.g. [-8, 7] at 4 bits).
    """
    lo, hi = int_range(bits)
    scaled = w.astype(jnp.float32) * (2.0 ** exponent)
    if rounding not in ("floor", "nearest"):
        raise ValueError(f"unknown rounding {rounding!r}")
    if stochastic_key is not None:  # beyond-paper: stochastic rounding option
        noise = jax.random.uniform(stochastic_key, w.shape)
        q = jnp.floor(scaled + noise)
    elif rounding == "nearest":
        q = jnp.floor(scaled + 0.5)
    else:
        q = jnp.floor(scaled)
    return QTensor.store(jnp.clip(q, lo, hi), exponent, bits=bits)


def choose_exponent(w: jnp.ndarray, *, bits: int = 8) -> int:
    """Largest y such that floor(max|w| * 2^y) does not saturate.

    The paper picks y by accuracy sweep (Table V); this is the analytic
    no-overflow bound used as the sweep's starting point.
    """
    import numpy as np

    maxabs = float(jnp.max(jnp.abs(w)))
    if maxabs == 0.0:
        return bits - 1
    return int(np.floor(np.log2((2 ** (bits - 1) - 1) / maxabs)))


def qmatmul(x: QTensor, w: QTensor, *, out_exponent: int | None = None,
            residual_bits: int = 16) -> QTensor:
    """Integer matmul with int32 accumulation and shift rescale.

    C_int32 = X_int8 @ W_int8 has exponent (x.e + w.e).  The result is
    shifted to ``out_exponent`` and clipped to the residual width (paper:
    INT16 intermediates).
    """
    xv, wv = x.int_values(), w.int_values()
    acc = jax.lax.dot_general(
        xv, wv,
        dimension_numbers=(((xv.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    acc_exp = x.exponent + w.exponent
    out_exponent = acc_exp if out_exponent is None else out_exponent
    shift = acc_exp - out_exponent
    acc = jnp.where(shift >= 0, acc >> shift, acc << (-shift)) if isinstance(shift, jnp.ndarray) \
        else (acc >> shift if shift >= 0 else acc << (-shift))
    lo, hi = (INT16_MIN, INT16_MAX) if residual_bits == 16 else (-(2**31), 2**31 - 1)
    dtype = jnp.int16 if residual_bits == 16 else jnp.int32
    return QTensor(values=jnp.clip(acc, lo, hi).astype(dtype),
                   exponent=out_exponent, bits=residual_bits)


def resident_values(w: QTensor) -> jnp.ndarray:
    """In-jit float view of a stored-integer leaf, fusion-isolated.

    Unpacks the nibble/int8 grid and applies the power-of-2 de-scale —
    both exact, so the VALUES equal the plan-time dequantisation bit for
    bit — behind an ``optimization_barrier`` that keeps the quantiser ops
    out of the model's fusion regions (the PR-2 lesson).  Note the
    whole-program caveat: merely compiling quantiser ops into the same
    XLA module can re-tile unrelated reductions (LayerNorm/softmax) on
    CPU, so the runtime Engine's bit-identity contract additionally runs
    the unpack as its own executable (``Engine.live_params``); this
    in-jit path serves direct model calls on packed trees, where
    value-exactness (not cross-program bit-identity) is the contract.
    """
    return jax.lax.optimization_barrier(w.dequantize())


def qt_einsum(eq: str, x: jnp.ndarray, w: QTensor) -> jnp.ndarray:
    """Einsum against a *stored-integer* QTensor weight (integer-resident
    linear layers — the Engine's lut/pallas weight path).

    The weight bytes the jitted program closes over stay int8 /
    nibble-packed int4; the float view is materialised per call by
    :func:`resident_values` (exact unpack + po2 de-scale, fusion-isolated),
    so logits are **bit-identical** to the dequantise-first float-matmul
    path while storage is dtype-true end to end.

    Integer activations (a QTensor ``x``) are the full-integer pipeline:
    route those through ``kernels.ops.int8_matmul`` (the Pallas
    int8 x int8 -> int32 kernel over the same stored operands) or
    :func:`qmatmul`; this helper is the float-activation contract.
    """
    if isinstance(x, QTensor):
        raise TypeError("qt_einsum is the float-activation path; integer "
                        "activations go through kernels.ops.int8_matmul / "
                        "quant.qmatmul on the same stored operands")
    return jnp.einsum(eq, x, resident_values(w))


# ---------------------------------------------------------------------------
# Full-integer execution: eq-9 activation quantiser + integer-executing
# einsum over the STORED payload (no float weight view, no unpack stage).
# The function names below are load-bearing: analysis.residency whitelists
# int->float casts by trace-time frame (`int_container` / `requant` /
# `gather_descale`), and perf.cost prices their ops as the `requant` class.
# ---------------------------------------------------------------------------

# f32 holds every integer up to 2^24 exactly; while K * 2^(xbits-1) *
# 2^(wbits-1) stays under this, an f32 GEMM over integer grids is
# bit-equal to int32 accumulation (measured ~1.7x faster than XLA:CPU's
# int8 dot_general at KWT shapes — the win the lut backend banks on).
_F32_EXACT = 1 << 24

# Below this many MACs a contraction is dispatch-dominated on XLA:CPU
# (an Eigen dot thunk + its weight-convert thunk cost more than the math);
# int_exec_einsum emits a fusable multiply-reduce instead.
_SMALL_MACS = 8192


def matmul_unrolled(xq: jnp.ndarray, wi: jnp.ndarray, k: int) -> jnp.ndarray:
    """K-loop of a trivial contraction unrolled into elementwise
    multiply-adds (the named frame lets repro.perf price the chain as
    matmul MACs rather than loose elementwise ops)."""
    acc = xq[..., 0:1] * wi[0]
    for i in range(1, k):
        acc = acc + xq[..., i:i + 1] * wi[i]
    return acc


def quantize_act(x: jnp.ndarray, exponent: int, *, bits: int = 8
                 ) -> jnp.ndarray:
    """eq 9 applied to a linear-layer input: the jitted per-layer
    activation quantiser of the integer-executing pipeline.

    Same semantics as the PTQ/QAT weight cast (``quantize_po2`` /
    ``recipe.po2_fake_quant`` with nearest rounding): scale by the
    power-of-2 input exponent (Table V: 2^5), floor with the half-LSB
    offset, saturate at the ``bits``-wide edges.  Returns the integer
    GRID in an f32 container (values in [lo, hi], exactly representable)
    so the downstream matmul runs exact integer math without an
    int->float cast in the plan.
    """
    lo, hi = int_range(bits)
    q = jnp.floor(x.astype(jnp.float32) * jnp.float32(2.0 ** exponent) + 0.5)
    return jnp.clip(q, lo, hi)


def int_container(w: QTensor) -> jnp.ndarray:
    """The stored integer grid in an f32 container — value-preserving
    (every ``bits``-wide integer is exact in f32), NOT a dequantisation:
    no scale is applied, the values stay on the integer lattice.  Named
    so the residency pass can tell this container widening apart from a
    float weight view."""
    return w.int_values().astype(jnp.float32)


def requant(acc: jnp.ndarray, x_exp: int, w_exp: int,
            axis_exponents: jnp.ndarray | None = None) -> jnp.ndarray:
    """Power-of-2 requantisation epilogue of the integer matmul: descale
    the accumulator by 2^-(x_exp+w_exp), then the per-output-channel
    refinements.  All multiplications are by powers of two — exact in
    f32 — so jnp and Pallas realisations produce bit-identical floats."""
    if jnp.issubdtype(acc.dtype, jnp.integer):
        acc = acc.astype(jnp.float32)
    out = acc * jnp.float32(2.0 ** (-(x_exp + w_exp)))
    if axis_exponents is not None:
        out = out * jnp.exp2(-axis_exponents.astype(jnp.float32))
    return out


def int_exec_supported(w, eq: str) -> bool:
    """Can ``int_exec_einsum`` run ``eq`` against ``w`` integer-only?

    Supported: rank-2 weights contracted on the activation's last axis,
    weight-first (``bsd,df->bsf``-family) or weight-last (the tied-
    embedding head ``...d,vd->...v``).  Per-channel ``axis_exponents``
    live on the weight's LAST axis, so the weight-last layout puts them
    on the contraction axis where they cannot fold into a post-matmul
    epilogue — those fall back to the float-view path (documented LM
    tied-head exception).
    """
    if not isinstance(w, QTensor) or len(w.shape) != 2:
        return False
    lhs, rhs = eq.split("->")[0].split(",")
    if len(rhs) != 2:
        return False
    if rhs[0] == lhs[-1]:                 # weight-first: per-channel
        return True                       # exps fold into the epilogue
    if rhs[1] == lhs[-1]:                 # weight-last (tied head)
        return w.axis_exponents is None
    return False


def int_exec_einsum(eq: str, x: jnp.ndarray, w: QTensor, *,
                    x_exp: int, x_bits: int = 8, residual_bits: int = 16,
                    use_kernel: bool = False, interpret: bool = True
                    ) -> jnp.ndarray:
    """Integer-executing linear layer: quantise the activation (eq 9),
    multiply against the STORED int8 / nibble-packed int4 payload, clip
    to the paper's INT16 residual, requantise.  No ``dequantize_tree``
    stage, no float weight view — the only float-producing op in the
    plan is the exact po2 :func:`requant` epilogue.

    ``use_kernel`` routes the matmul through the Pallas int8 x int8 ->
    int32 kernel (``kernels.ops.int8_matmul``) — the compiled-Mosaic
    path.  In interpret mode the jnp realisation below IS the kernel's
    reference semantics (same integer accumulation, same int16 clip,
    same epilogue order), bit-identical by construction and without the
    kernel's (8,128)/(128,128) padding round-trip per call.
    """
    lhs, rhs = eq.split("->")[0].split(",")
    transpose_w = rhs[0] != lhs[-1]       # weight-last (tied head) layout
    k = int(x.shape[-1])
    xq = quantize_act(x, x_exp, bits=x_bits)
    if use_kernel and not interpret and not transpose_w:
        from repro.kernels import ops as _kops
        lead = x.shape[:-1]
        out2 = _kops.int8_matmul(xq.reshape(-1, k).astype(jnp.int8), w,
                                 x_exp=x_exp,
                                 residual_bits=residual_bits,
                                 interpret=interpret)
        # the barrier stops XLA from folding this reshape into the
        # consumers: LayerNorm then reduces the same [..., d] layout as
        # the jnp plan's dot output, keeping the kernel plan's logits
        # bit-identical to it (a flattened 2-D reduce sums in another
        # order on the TPU)
        return jax.lax.optimization_barrier(
            out2.reshape(*lead, out2.shape[-1]))
    # contract the LAST activation axis in place — no flatten/unflatten
    # round-trip, so XLA keeps float-plan layouts downstream (a 2D
    # reshape here costs two copy fusions per linear and forces a
    # strided layout on the attention batch dots; measured ~3x on the
    # scores matmul).  Bit-identical: each output element is the same
    # ordered K-reduction either way.
    dims = (((xq.ndim - 1,), (0,)), ((), ()))
    macs = xq.size // k * k * int(w.shape[0 if transpose_w else 1])
    if k * 2 ** (x_bits - 1) * 2 ** (w.bits - 1) <= _F32_EXACT:
        # exact integer math in f32 containers (see _F32_EXACT)
        wi = int_container(w)
        if transpose_w:
            wi = wi.T
        if macs <= _SMALL_MACS:
            # trivial contraction (the classifier head): unroll the K-loop
            # into elementwise multiply-adds so XLA fuses the s8->f32
            # container widening, the products, the int16 clip and the
            # requant epilogue into the neighbouring fusions — zero
            # standalone thunks, vs a weight-convert thunk plus a dot
            # thunk (or a multiply fusion plus a reduce thunk for a
            # sum-over-axis form).  Every product and partial sum is an
            # exact integer under _F32_EXACT, so any summation order
            # gives the same value — bit-identical to the dot.
            acc = matmul_unrolled(xq, wi, k)
        else:
            acc = jax.lax.dot_general(xq, wi, dims,
                                      preferred_element_type=jnp.float32)
    else:
        # contraction too long for the f32 mantissa: true int32 path
        wl = w.int_values()
        if transpose_w:
            wl = wl.T
        acc = jax.lax.dot_general(xq.astype(jnp.int32), wl, dims,
                                  preferred_element_type=jnp.int32)
    if residual_bits == 16:
        acc = jnp.clip(acc, INT16_MIN, INT16_MAX)
    axis = None if transpose_w else w.axis_exponents
    return requant(acc, x_exp, w.exponent, axis)


def int_exec_qkv(x: jnp.ndarray, ws, *, x_exp: int, x_bits: int = 8,
                 residual_bits: int = 16):
    """Fused Q/K/V integer projection: ONE int8 x int8 dot over the
    three stored payloads concatenated on the output axis, with each
    leaf's scalar-exponent delta folded into the per-column requant
    epilogue.  Bitwise equal to three separate :func:`int_exec_einsum`
    calls — an f32 dot's K-reduction is per-column independent, and the
    po2 column scale 2^-(x+e0+delta) == 2^-(x+e_leaf)·2^-axis_leaf
    exactly — at a third of the dot/convert thunk dispatches.

    Returns the per-leaf outputs (split back at the leaf widths).
    """
    k = int(x.shape[-1])
    xq = quantize_act(x, x_exp, bits=x_bits)
    dims = (((xq.ndim - 1,), (0,)), ((), ()))
    wide = max(w.bits for w in ws)
    if k * 2 ** (x_bits - 1) * 2 ** (wide - 1) <= _F32_EXACT:
        wi = jnp.concatenate([int_container(w) for w in ws], axis=-1)
        acc = jax.lax.dot_general(xq, wi, dims,
                                  preferred_element_type=jnp.float32)
    else:
        wl = jnp.concatenate([w.int_values() for w in ws], axis=-1)
        acc = jax.lax.dot_general(xq.astype(jnp.int32), wl, dims,
                                  preferred_element_type=jnp.int32)
    if residual_bits == 16:
        acc = jnp.clip(acc, INT16_MIN, INT16_MAX)
    e0 = ws[0].exponent
    if all(w.exponent == e0 and w.axis_exponents is None for w in ws):
        axis = None
    else:
        cols = []
        for w in ws:
            delta = jnp.full((w.shape[-1],), w.exponent - e0, jnp.float32)
            if w.axis_exponents is not None:
                delta = delta + w.axis_exponents.astype(jnp.float32)
            cols.append(delta)
        axis = jnp.concatenate(cols)
    out = requant(acc, x_exp, e0, axis)
    splits = np.cumsum([w.shape[-1] for w in ws])[:-1].tolist()
    return jnp.split(out, splits, axis=-1)


def gather_descale(w: QTensor, idx: jnp.ndarray) -> jnp.ndarray:
    """Embedding lookup against the stored payload: gather integer ROWS,
    then descale only what was looked up.  The full table never
    materialises as float — the LM embed family's integer-executing
    replacement for dequantise-first."""
    rows = jnp.take(w.int_values(), idx, axis=0)
    out = rows.astype(jnp.float32) * jnp.float32(2.0 ** (-w.exponent))
    if w.axis_exponents is not None:
        out = out * jnp.exp2(-w.axis_exponents.astype(jnp.float32))
    return out


def dequantize_tree(tree: Pytree) -> Pytree:
    """Replace every QTensor leaf with its float32 dequantisation."""
    return jax.tree.map(
        lambda leaf: leaf.dequantize() if isinstance(leaf, QTensor) else leaf,
        tree, is_leaf=lambda leaf: isinstance(leaf, QTensor))


def quantize_tree(params: Pytree, *, weight_exponent: int = 6,
                  bits: int = 8, skip_norm_scales: bool = True,
                  rounding: str = "nearest") -> Pytree:
    """PTQ a parameter pytree with one global weight exponent (Table V row).

    LayerNorm/RMSNorm scale+shift vectors stay float (paper §IV) — detected
    as rank<=1 leaves when ``skip_norm_scales``.  Whole-model PTQ rounds to
    nearest (half-LSB offset before the eq-9 floor): the bare floor's
    correlated -LSB/2 bias visibly degrades LM logit ranks at the Table V
    exponents; pass ``rounding="floor"`` for the bit-exact paper cast.
    """
    def one(leaf):
        if not isinstance(leaf, jnp.ndarray) or not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        if skip_norm_scales and leaf.ndim <= 1:
            return leaf
        return quantize_po2(leaf, weight_exponent, bits=bits, rounding=rounding)

    return jax.tree.map(one, params)


def tree_quantized_bytes(tree: Pytree) -> tuple[int, int]:
    """(quantised_bytes, float_bytes) of a (partially) quantised tree.

    ``quantised_bytes`` is the TRUE packed storage count — nibble-packed
    bytes for ``bits<=4`` leaves plus any per-channel exponent bytes —
    i.e. the integer image a device would actually flash, not a
    dtype-derived fiction.
    """
    qb = fb = 0
    for leaf in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            qb += leaf.stored_bytes
        elif isinstance(leaf, jnp.ndarray):
            fb += leaf.size * leaf.dtype.itemsize
    return qb, fb
