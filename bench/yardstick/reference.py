"""Plain references the served logits are compared with, in numpy.

Written from the definitions and sharing no code with the program:

* :func:`mfcc` -- the MFCC frontend: symmetric Hann window, power
  spectrum of a zero-padded real FFT, HTK-mel triangular filterbank,
  natural log with a floor, orthonormal DCT-II; at the numerics a
  configuration states, the operands of the mel and DCT matmuls rounded
  to ``frontend_operands``.
* :func:`kwt_logits` -- the Keyword Transformer (Berg et al.,
  arXiv:2104.00769, section 3; KWT-Tiny, arXiv:2407.16026, section II):
  per-time-step patches, a linear projection, a class token, learned
  positions, post-norm blocks of full multi-head attention and an MLP,
  and a class-token head.

:func:`kwt_logits` computes the model at the numerics a configuration
states (its ``numerics`` block, read by :func:`Numerics.stated`):

* every weight matrix (and the positions) and every linear layer's input
  rounded, half up, to a ``bits``-wide two's-complement grid of step
  ``2**-weight_exponent`` or ``2**-input_exponent``, each matmul's
  accumulator, in units of the product of the two steps, clipped to
  ``acc_bits``; or, where the configuration states no grid
  (``weight_bits`` null: the float plan), each linear layer's operands
  rounded to ``linear_operands`` and the products summed exactly;
* the operands of the two attention matmuls (queries and keys; weights
  and values) rounded to ``attention_operands``, the products summed
  exactly;
* softmax and GELU as the paper's lookup tables (arXiv:2407.16026,
  section VI, eqs 10-13), rebuilt here from those equations: the
  softmax in Q8.24 fixed point through an e^-z table and a range-reduced
  1/z table, GELU as the nearest of evenly spaced exact samples;
* everything else (biases, residual adds, LayerNorm, the attention
  scale) exactly, or rounded to ``float_dtype`` where that is lower than
  float32.

A control is the same function one precision step below what is stated
(:meth:`Numerics.lowered`): it has to fail the comparison.
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np
from scipy.special import erf


# -- frontend ----------------------------------------------------------------

def hann(n: int) -> np.ndarray:
    """Symmetric Hann window of ``n`` points."""
    k = np.arange(n)
    return 0.5 - 0.5 * np.cos(2.0 * np.pi * k / (n - 1))


def _mel(f):
    return 2595.0 * np.log10(1.0 + np.asarray(f, np.float64) / 700.0)


def _hz(m):
    return 700.0 * (10.0 ** (np.asarray(m, np.float64) / 2595.0) - 1.0)


def mel_filterbank(fr: dict) -> np.ndarray:
    """[n_fft // 2 + 1, n_mels] triangles with edges evenly spaced on the
    HTK mel scale between ``fmin`` and ``fmax``."""
    freqs = np.linspace(0.0, fr["sample_rate"] / 2.0, fr["n_fft"] // 2 + 1)
    edges = _hz(np.linspace(_mel(fr["fmin"]), _mel(fr["fmax"]),
                            fr["n_mels"] + 2))
    lo, mid, hi = edges[:-2, None], edges[1:-1, None], edges[2:, None]
    rise = (freqs[None] - lo) / np.maximum(mid - lo, 1e-9)
    fall = (hi - freqs[None]) / np.maximum(hi - mid, 1e-9)
    return np.maximum(0.0, np.minimum(rise, fall)).T


def dct2(n_in: int, n_out: int) -> np.ndarray:
    """Orthonormal DCT-II as an [n_in, n_out] matrix."""
    n = np.arange(n_in)[:, None]
    k = np.arange(n_out)[None, :]
    m = np.sqrt(2.0 / n_in) * np.cos(np.pi * (2 * n + 1) * k / (2 * n_in))
    m[:, 0] /= np.sqrt(2.0)
    return m


def mfcc(audio: np.ndarray, fr: dict,
         operands: str | None = None) -> np.ndarray:
    """Frames of ``audio`` [..., n] -> MFCCs [..., n_frames, n_mfcc].

    Frame ``j`` is samples ``[j * hop_len, j * hop_len + frame_len)``; the
    caller passes the left context with the audio.  ``operands`` rounds
    the operands of the mel and DCT matmuls to that type, the products
    summed exactly; None keeps them exact.
    """
    def op(x):
        return x if operands is None else round_to(x, operands)

    audio = np.asarray(audio, np.float64)
    n_frames = (audio.shape[-1] - fr["frame_len"]) // fr["hop_len"] + 1
    idx = (np.arange(n_frames)[:, None] * fr["hop_len"]
           + np.arange(fr["frame_len"])[None, :])
    frames = audio[..., idx] * hann(fr["frame_len"])
    spec = np.fft.rfft(frames, n=fr["n_fft"], axis=-1)
    power = spec.real ** 2 + spec.imag ** 2
    mel = op(power) @ op(mel_filterbank(fr))
    logmel = np.log(np.maximum(mel, fr["log_floor"]))
    return op(logmel) @ op(dct2(fr["n_mels"], fr["n_mfcc"]))


# -- model -------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Numerics:
    """What a configuration states about how the model computes."""

    bits: int | None                 # None: no integer grid
    weight_exponent: int | None
    input_exponent: int | None
    acc_bits: int | None
    linear_operands: str | None      # a dtype name, where there is no grid
    attention_operands: str          # a dtype name, e.g. ``bfloat16``
    float_dtype: str                 # ``float32``, or lower in a control
    softmax_lut: dict | None         # None: exact softmax
    gelu_lut: dict | None            # None: exact GELU

    @classmethod
    def stated(cls, num: dict) -> "Numerics":
        """From a configuration file's ``numerics`` block."""
        return cls(bits=num["weight_bits"],
                   weight_exponent=num.get("weight_exponent"),
                   input_exponent=num.get("input_exponent"),
                   acc_bits=num.get("acc_bits"),
                   linear_operands=num.get("linear_operands"),
                   attention_operands=num["attention_operands"],
                   float_dtype=num["float_dtype"],
                   softmax_lut=num.get("softmax_lut"),
                   gelu_lut=num.get("gelu_lut"))

    def lowered(self, part: str) -> "Numerics":
        """One step below what is stated, in ``part``: ``grid`` (int8 to
        int4 with the same ranges; with no grid, the linear layers'
        operands, bfloat16 to float8 e4m3), ``attention`` (bfloat16
        operands to float8 e4m3) or ``float`` (float32 to bfloat16)."""
        if part == "grid" and self.bits is None:
            return dataclasses.replace(
                self, linear_operands=LOWER[self.linear_operands])
        if part == "grid":
            cut = self.bits - 4
            return dataclasses.replace(
                self, bits=4, weight_exponent=self.weight_exponent - cut,
                input_exponent=self.input_exponent - cut)
        if part == "attention":
            return dataclasses.replace(
                self, attention_operands=LOWER[self.attention_operands])
        if part == "float":
            return dataclasses.replace(
                self, float_dtype=LOWER[self.float_dtype])
        raise ValueError(f"unknown part {part!r}")


# the next precision down, the step a faster plan would take
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def round_to(x, dtype: str):
    """``x`` rounded to the nearest ``dtype`` (ties to even, saturating
    where the type has no infinity), as float64."""
    dt = np.dtype(getattr(ml_dtypes, dtype, dtype))
    big = float(ml_dtypes.finfo(dt).max)
    x = np.clip(np.asarray(x, np.float64), -big, big).astype(np.float32)
    return x.astype(dt).astype(np.float64)


def _grid(x, bits, exp):
    """``x`` as whole multiples of ``2**-exp``, rounded half up and
    saturated to a ``bits``-wide signed integer."""
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    return np.clip(np.floor(x * 2.0 ** exp + 0.5), lo, hi)


def _linear(x, w, b, num: Numerics):
    if num.bits is None:
        return round_to(x, num.linear_operands) \
            @ round_to(w, num.linear_operands) + b
    acc = _grid(x, num.bits, num.input_exponent) \
        @ _grid(w, num.bits, num.weight_exponent)
    lim = 2 ** (num.acc_bits - 1)
    acc = np.clip(acc, -lim, lim - 1)
    return acc * 2.0 ** -(num.input_exponent + num.weight_exponent) + b


def _layernorm(x, scale, bias, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * scale + bias


def softmax_exact(s):
    e = np.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def softmax_lut(s, lut: dict):
    """The paper's fixed-point softmax over the last axis (eqs 10-12).

    In Q8.24 (``frac_bits``): z = clip(max - s, 0, ``exp_range``); the
    numerator is the table entry e^-(i / ``bins_per_unit``) at
    i = floor(z * bins_per_unit); the row sum, with each numerator first
    shifted right (rounded) by ``max(0, ceil(log2 K) - 6)`` bits so that
    a sum over K keys stays in int32; its reciprocal from the table
    1/((i + 1) / bins_per_unit) at the sum's mantissa in [1, 2), shifted
    back by its exponent and the pre-shift; the numerator times the
    reciprocal as the sum of the products of their ``frac_bits / 2``-bit
    halves, each shifted into place and floored."""
    one = 1 << lut["frac_bits"]
    bins, rng = lut["bins_per_unit"], lut["exp_range"]
    n = int(rng * bins)
    shift = lut["frac_bits"] - int(np.log2(bins))
    exp_tab = np.round(np.exp(-np.arange(n) / bins) * one).astype(np.int64)
    inv_tab = np.round(bins / (np.arange(n) + 1.0) * one).astype(np.int64)
    z = np.clip(s.max(-1, keepdims=True) - s, 0.0, rng)
    z_q = np.rint(z * one).astype(np.int64)
    num_q = exp_tab[np.clip(z_q >> shift, 0, n - 1)]
    k = s.shape[-1]
    pre = max(0, int(np.ceil(np.log2(max(k, 1)))) - 6)
    shifted = (num_q + (1 << (pre - 1))) >> pre if pre else num_q
    s_q = shifted.sum(-1, keepdims=True)
    t = np.floor(np.log2(s_q)).astype(np.int64) - lut["frac_bits"]
    mant = np.where(t >= 0, s_q >> np.maximum(t, 0), s_q << np.maximum(-t, 0))
    inv_m = inv_tab[np.clip((mant >> shift) - 1, 0, n - 1)]
    inv_q = np.where(t >= 0, inv_m >> np.maximum(t, 0),
                     np.minimum(inv_m << np.maximum(-t, 0), 2 ** 31 - 1))
    inv_q = inv_q >> pre
    half = lut["frac_bits"] // 2                 # the product in limbs
    lo_mask = (1 << half) - 1
    ah, al, bh, bl = num_q >> half, num_q & lo_mask, inv_q >> half, \
        inv_q & lo_mask
    out_q = ah * bh + ((ah * bl + al * bh) >> half) \
        + ((al * bl) >> lut["frac_bits"])
    return out_q / one


def gelu_exact(x):
    return 0.5 * x * (1.0 + erf(x / np.sqrt(2.0)))


def gelu_lut(x, lut: dict):
    """The paper's GELU (eq 13): x above ``hi``, 0 below ``lo``, and
    between them the nearest of ``entries`` exact samples evenly spaced
    over [lo, hi], each held as a float32."""
    n, lo, hi = lut["entries"], lut["lo"], lut["hi"]
    tab = round_to(gelu_exact(np.linspace(lo, hi, n)), "float32")
    idx = np.clip(np.rint((x - lo) * ((n - 1) / (hi - lo))), 0, n - 1)
    return np.where(x > hi, x, np.where(x < lo, 0.0,
                                        tab[idx.astype(np.int64)]))


def kwt_logits(w: dict, frames: np.ndarray, model: dict,
               num: Numerics) -> np.ndarray:
    """KWT over time-major MFCC windows ``frames`` [P, T, F] -> [P, C] at
    the numerics ``num``.

    ``w`` holds float64 numpy weights in the program's layout
    (``proj_w``, ``pos``, ``blocks[i]['attn']['wq']``, ...)."""
    def fl(x):          # a float intermediate, at the stated float type
        return x if num.float_dtype == "float32" \
            else round_to(x, num.float_dtype)

    def op(x):          # an attention matmul's operand
        return round_to(x, num.attention_operands)

    softmax = softmax_exact if num.softmax_lut is None \
        else (lambda s: softmax_lut(s, num.softmax_lut))
    gelu = gelu_exact if num.gelu_lut is None \
        else (lambda x: gelu_lut(x, num.gelu_lut))

    x = fl(_linear(np.asarray(frames, np.float64), w["proj_w"], w["proj_b"],
                   num))
    p = x.shape[0]
    cls = np.broadcast_to(w["cls"], (p, 1, x.shape[-1]))
    pos = w["pos"] if num.bits is None else \
        _grid(w["pos"], num.bits, num.weight_exponent) \
        * 2.0 ** -num.weight_exponent
    x = fl(np.concatenate([cls, x], axis=1) + pos)
    s = x.shape[1]
    h, dh = model["n_heads"], model["head_dim"]
    for blk in w["blocks"]:
        a = blk["attn"]
        q, k, v = (op(fl(_linear(x, a[f"w{n}"], a[f"b{n}"], num))
                      .reshape(p, s, h, dh)) for n in "qkv")
        scores = fl(np.einsum("pqhd,pkhd->phqk", q, k) / np.sqrt(dh))
        att = op(softmax(scores))
        o = fl(np.einsum("phqk,pkhd->pqhd", att, v).reshape(p, s, h * dh))
        x = fl(_layernorm(fl(x + fl(_linear(o, a["wo"], a["bo"], num))),
                          blk["ln1"]["scale"], blk["ln1"]["bias"]))
        m = blk["mlp"]
        f = fl(_linear(fl(gelu(fl(_linear(x, m["w1"], m["b1"], num)))),
                       m["w2"], m["b2"], num))
        x = fl(_layernorm(fl(x + f), blk["ln2"]["scale"], blk["ln2"]["bias"]))
    return _linear(x[:, 0], w["head_w"], w["head_b"], num)
