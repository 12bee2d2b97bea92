"""Work per lane-hop of KWT, counted from the algorithm's shapes.  (An LM
architecture counts its own, in ``bench/arch/<name>.py``.)

These counts are what the model needs, not what an implementation
executes: a padded tile, a recomputation or an unrolled multiply-add
chain does not raise them.  One multiply-add is two operations.
"""

from __future__ import annotations


def _seq(model: dict) -> int:
    return model["input_dim"][1] + 1          # time patches + class token


def encoder_flops(model: dict) -> int:
    """Matmul operations of one pass of the encoder over the window."""
    s, d, ff = _seq(model), model["d_model"], model["d_ff"]
    inner = model["n_heads"] * model["head_dim"]
    per_layer = (2 * s * d * 3 * inner        # Q, K, V projections
                 + 2 * s * s * inner          # scores
                 + 2 * s * s * inner          # attention-weighted values
                 + 2 * s * inner * d          # output projection
                 + 2 * 2 * s * d * ff)        # MLP up and down
    return model["n_layers"] * per_layer


def embed_flops(model: dict, hops: int = 1) -> int:
    """Patch embedding of the ``hops`` frames a step brings."""
    return 2 * hops * model["input_dim"][0] * model["d_model"]


def head_flops(model: dict) -> int:
    return 2 * model["d_model"] * model["n_classes"]


def step_flops(model: dict, hops: int = 1) -> int:
    """Model matmul operations for one lane and one step of ``hops``
    hops: the new frames' embedding, the encoder over the window, the
    head."""
    return embed_flops(model, hops) + encoder_flops(model) + head_flops(model)


def weight_bytes(model: dict, weight_bits: int) -> int:
    """Stored bytes of the encoder's weights: matrices at
    ``weight_bits``, vectors (biases, norms) at 4 bytes."""
    d, ff = model["d_model"], model["d_ff"]
    inner = model["n_heads"] * model["head_dim"]
    seq = _seq(model)
    mats = model["n_layers"] * (4 * d * inner + 2 * d * ff) \
        + seq * d + d * model["n_classes"]
    vecs = model["n_layers"] * (3 * inner + d + ff + d + 4 * d) \
        + d + model["n_classes"]
    return mats * weight_bits // 8 + 4 * vecs


def encoder_min_bytes(model: dict, lanes: int, weight_bits: int) -> int:
    """Least bytes one encoder step over ``lanes`` lanes moves to and from
    memory: each lane's window in (float32), its logits out, and the
    weights once.  Everything in between can stay on the chip."""
    window = model["input_dim"][1] * model["d_model"] * 4
    return lanes * (window + 4 * model["n_classes"]) \
        + weight_bytes(model, weight_bits)
