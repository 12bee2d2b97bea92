"""Work per lane-hop of KWT, and per token of a dense decoder LM, counted
from the algorithm's shapes.

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


# -- dense decoder LM ----------------------------------------------------------

def _lm_widths(model: dict):
    return (model["d_model"], model["n_heads"] * model["head_dim"],
            model["n_kv_heads"] * model["head_dim"], model["d_ff"])


def lm_layer_weights(model: dict) -> int:
    """Entries of one layer's matrices: Q, K, V, output, SwiGLU gate, up,
    down."""
    d, q, kv, ff = _lm_widths(model)
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def lm_tokens_flops(model: dict, start: int, count: int,
                    head: bool) -> int:
    """Matmul operations of ``count`` consecutive tokens of one sequence
    at positions ``start`` .. ``start + count - 1``, each attending to
    itself and every position before it (scores and weighted values);
    with ``head``, each token's logits too.  Padding and positions past
    the sequence are not work."""
    d, q, _, _ = _lm_widths(model)
    keys = count * start + count * (count + 1) // 2
    per_layer = 2 * count * lm_layer_weights(model) + 2 * 2 * keys * q
    logits = 2 * count * d * model["vocab_size"] if head else 0
    return model["n_layers"] * per_layer + logits


def lm_weight_bytes(model: dict, weight_bytes: int, norm_bytes: int) -> int:
    """Bytes of the weights one decode step reads whole: every layer's
    matrices and norms, the final norm and the head (the embedding table
    is read only at the rows looked up)."""
    d = model["d_model"]
    return (model["n_layers"] * lm_layer_weights(model)
            + d * model["vocab_size"]) * weight_bytes \
        + (2 * model["n_layers"] + 1) * d * norm_bytes


def lm_decode_min_bytes(model: dict, depths, act_bytes: int,
                        weight_bytes: int, norm_bytes: int) -> int:
    """Least bytes one decode step moves for lanes at cache positions
    ``depths`` (the position each lane's new token takes): the weights
    once; for each lane its embedding row, the keys and values of its
    valid earlier positions read, its new ones written, and its logits
    out.  Cache slots past a lane's depth, and lanes not serving a
    request, are not counted."""
    d, _, kv, _ = _lm_widths(model)
    kv_token = model["n_layers"] * 2 * kv * act_bytes
    per_lane = kv_token + d * weight_bytes + model["vocab_size"] * act_bytes
    return lm_weight_bytes(model, weight_bytes, norm_bytes) \
        + kv_token * int(sum(depths)) + per_lane * len(depths)
