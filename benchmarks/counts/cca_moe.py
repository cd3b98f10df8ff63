"""Operations and bytes the decoder of compressed convolutional attention
layers over a top-1 expert layer (``reference/cca_moe.py``) REQUIRES, from
the configuration's ``fields`` alone.  The yardstick: kept with the
benchmark so that no PR that claims a gain can change it.  ``fields`` count
what THIS CHIP holds: the key ``num_experts`` is the routed experts held
here, ``router_width`` the experts of the whole layer.

At the published widths (hidden 2048; 8 query and 2 KV heads of 128, so
1,280 latent channels in 10 groups; expert width 2048, 16 experts, top-1;
router hidden 256; tied vocabulary 262,272), 16 layers, all 16 experts
held: a layer's attention holds 2048 x 1536 + 1024 x 2048 projections,
5,120 + 327,680 conv weights and 2 temperatures = 5,575,682, its router
659,984, its experts 16 x 12,582,912 = 201,326,592, its two norms 4,096:
207,566,354; 16 layers 3,321,061,664, the embedding 537,133,056, the final
norm 2,048: 3,858,196,768 parameters = 7.716 GB of bf16.  A token caches
2 x 2 x 128 x 2 B = 1,024 B a layer, 16,384 B; a REQUEST holds in every
layer a tail of 2 x 1,280 + 128 = 2,688 elements, 5,376 B.
"""

from __future__ import annotations


def _dims(fields: dict):
    """(hidden, query heads, KV heads, head dim, latent channels)."""
    h = int(fields["hidden_size"])
    nq = int(fields["num_attention_heads"])
    nkv = int(fields["num_key_value_heads"])
    hd = int(fields.get("head_dim") or h // nq)
    return h, nq, nkv, hd, (nq + nkv) * hd


def conv_weight_count(fields: dict) -> int:
    """Both stages: the depthwise taps and bias, the grouped stage's two
    (hd, hd) matrices a group and its bias."""
    _, nq, nkv, hd, C = _dims(fields)
    return 4 * C + 2 * (nq + nkv) * hd * hd


def attention_weight_count(fields: dict) -> int:
    """q, k and both value projections, o, the convolutions, a temperature
    a KV head."""
    h, nq, nkv, hd, C = _dims(fields)
    return h * (C + 2 * hd) + nq * hd * h + conv_weight_count(fields) + nkv


def router_weight_count(fields: dict) -> int:
    """Down-projection, two hidden layers and the output layer with their
    biases."""
    h, R = int(fields["hidden_size"]), int(fields["router_hidden_size"])
    W = int(fields["router_width"])
    return h * R + 2 * (R * R + R) + R * W + W


def expert_weight_count(fields: dict) -> int:
    """One routed expert: three matrices of hidden x width."""
    return 3 * int(fields["hidden_size"]) \
        * int(fields["moe_intermediate_size"])


def layer_weight_count(fields: dict) -> int:
    return attention_weight_count(fields) + router_weight_count(fields) \
        + int(fields["num_experts"]) * expert_weight_count(fields) \
        + 2 * int(fields["hidden_size"])


def param_count(fields: dict) -> int:
    h = int(fields["hidden_size"])
    return int(fields["num_hidden_layers"]) * layer_weight_count(fields) \
        + int(fields["vocab_size"]) * h + h


def model_flops_per_token(fields: dict, seq_len: int) -> float:
    """Forward FLOPs a token: 2 a weight it is multiplied by (the
    projections, the conv's grouped stage, the router, ONE expert, the tied
    head) and causal attention at the mean context ``seq_len / 2``."""
    h, nq, nkv, hd, C = _dims(fields)
    dense = h * (C + 2 * hd) + nq * hd * h + 2 * (nq + nkv) * hd * hd \
        + router_weight_count(fields) + expert_weight_count(fields)
    L = int(fields["num_hidden_layers"])
    return 2.0 * (L * dense + int(fields["vocab_size"]) * h) \
        + L * 4.0 * nq * hd * seq_len / 2


def kv_bytes_per_token(fields: dict, itemsize: int = 2) -> int:
    """K and V rows of (KV heads, head dim) in every layer."""
    _, _, nkv, hd, _ = _dims(fields)
    return int(fields["num_hidden_layers"]) * 2 * nkv * hd * itemsize


def tail_bytes(fields: dict, itemsize: int = 2) -> int:
    """One request's tail in ONE layer: the last two latents and the last
    shifted value half."""
    _, _, _, hd, C = _dims(fields)
    return (2 * C + hd) * itemsize


def expert_step_bytes(fields: dict, experts_touched: float,
                      itemsize: int = 2) -> float:
    """Bytes the held experts' product must read in one step: the three
    matrices of every held expert that got a token, over all layers."""
    return experts_touched * expert_weight_count(fields) * itemsize


def cca_conv_bytes(fields: dict, rows: float, slots: float,
                   itemsize: int = 2) -> float:
    """Bytes the convolutions, the q-k mean, the value shift and the
    tail's update of one launch must move, over all layers: the two
    stages' weights once, every live slot's tail read once and written
    once, and per row the latents and the shifted value half in and q', k'
    and v out at the served dtype."""
    _, _, nkv, hd, C = _dims(fields)
    return int(fields["num_hidden_layers"]) * (
        conv_weight_count(fields) * itemsize
        + slots * 2 * tail_bytes(fields, itemsize)
        + rows * (2 * C + hd + nkv * hd) * itemsize)


def decode_step_bytes(fields: dict, valid_kv_tokens: float,
                      itemsize: int = 2, live_slots: float = 0.0,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must move: every weight but the held experts
    that got no token this step (the embedding table IS the head, read
    whole), the K/V rows the live requests hold, and the live slots' tails
    (read and written).  ``experts_touched``: held experts with a token,
    summed over the layers of one step (default: all)."""
    held = int(fields["num_hidden_layers"]) * int(fields["num_experts"])
    idle = held - (held if experts_touched is None else experts_touched)
    weights = param_count(fields) - idle * expert_weight_count(fields)
    return weights * itemsize \
        + valid_kv_tokens * kv_bytes_per_token(fields, itemsize) \
        + int(fields["num_hidden_layers"]) * live_slots * 2 \
        * tail_bytes(fields, itemsize)


def paged_decode_attention_flops(fields: dict, live_tokens: float) -> float:
    """FLOPs the decode attention needs in one step for ``live_tokens``
    cached positions over all slots: per query head and key 2 x hd for the
    score and 2 x hd for the value, in every layer."""
    _, nq, _, hd, _ = _dims(fields)
    return int(fields["num_hidden_layers"]) * live_tokens * 4.0 * nq * hd


def paged_decode_attention_bytes(fields: dict, live_tokens: float,
                                 slots: float, itemsize: int = 2) -> float:
    """Bytes the same step must move: each live K and V row once, and per
    slot the heads' queries in and their outputs out (float32)."""
    _, nq, nkv, hd, _ = _dims(fields)
    return int(fields["num_hidden_layers"]) * (
        live_tokens * 2 * nkv * hd * itemsize
        + slots * nq * hd * (itemsize + 4))
