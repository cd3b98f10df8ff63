"""Operations and bytes the hybrid decoder of gated delta-rule layers and
output-gated full-attention layers with an expert layer under every mixer
(``reference/gdn_moe.py``) REQUIRES, from the configuration's ``fields``
alone.  The yardstick: kept with the benchmark so that no PR that claims a
gain can change it.  ``fields`` count what THIS CHIP holds: the key
``num_experts`` is the routed experts held here, ``router_width`` the
experts of the whole layer.

At the published widths (hidden 2048; full attention 16 heads of 256 with
2 KV heads and a query projection twice as wide, for the output gate;
linear layers of 16 key heads and 32 value heads of 128 with a conv of
width 4 over 8,192 channels; experts of width 512, router 512, top-10, one
shared expert of 512; vocabulary 151,936), 24 layers = six periods of
three linear layers and one full-attention layer, 32 held experts a layer:
3.91 G parameters = 7.82 GB of bf16; a token caches 2 x 2 x 256 x 2 B =
2,048 B in each of the 6 full-attention layers (12,288 B) and nothing in
the 18 linear ones, each of which holds per REQUEST a float32 state of
32 x 128 x 128 x 4 B = 2,097,152 B and a bf16 conv tail of 3 x 8,192 x 2 B
= 49,152 B.
"""

from __future__ import annotations

#: rows of one sub-chunk of the chunked scan the counts below are stated
#: for (the program's ``gdn_hybrid.SCAN_CHUNK``)
SCAN_CHUNK = 64


def _layers(fields: dict) -> tuple[int, int]:
    """(linear layers, full-attention layers): layer i, 0-based, is full
    where ``(i + 1) % full_attention_interval == 0``."""
    n_full = int(fields["num_hidden_layers"]) \
        // int(fields["full_attention_interval"])
    return int(fields["num_hidden_layers"]) - n_full, n_full


def _attn_dims(fields: dict):
    h = int(fields["hidden_size"])
    nq = int(fields["num_attention_heads"])
    return h, nq, int(fields["num_key_value_heads"]), \
        int(fields.get("head_dim") or h // nq)


def _lin_dims(fields: dict):
    """(key heads, value heads, key dim, value dim, conv width)."""
    return (int(fields["linear_num_key_heads"]),
            int(fields["linear_num_value_heads"]),
            int(fields["linear_key_head_dim"]),
            int(fields["linear_value_head_dim"]),
            int(fields["linear_conv_kernel_dim"]))


def conv_channels(fields: dict) -> int:
    nk, nv, dk, dv, _ = _lin_dims(fields)
    return 2 * nk * dk + nv * dv


def full_layer_weight_count(fields: dict) -> int:
    """q with its gate (twice as wide), k, v, o, the two per-head norms."""
    h, nq, nkv, hd = _attn_dims(fields)
    return h * hd * (3 * nq + 2 * nkv) + 2 * hd


def linear_layer_weight_count(fields: dict) -> int:
    """q, k, v, gate and out projections; the two per-head scalars'
    projections, ``A_log``, ``dt_bias``; the conv; the per-head norm."""
    h = int(fields["hidden_size"])
    _, nv, _, dv, K = _lin_dims(fields)
    C = conv_channels(fields)
    return h * (C + nv * dv) + nv * dv * h + 2 * h * nv + 2 * nv + K * C + dv


def expert_weight_count(fields: dict) -> int:
    """One routed expert: three matrices of hidden x width."""
    return 3 * int(fields["hidden_size"]) \
        * int(fields["moe_intermediate_size"])


def moe_rest_weight_count(fields: dict) -> int:
    """What an expert layer holds beside its routed experts: the router,
    the shared expert and its gate; and the layer's two norms."""
    h = int(fields["hidden_size"])
    return h * int(fields["router_width"]) \
        + 3 * h * int(fields["shared_expert_intermediate_size"]) + h + 2 * h


def param_count(fields: dict) -> int:
    h = int(fields["hidden_size"])
    n_lin, n_full = _layers(fields)
    common = moe_rest_weight_count(fields) \
        + int(fields["num_experts"]) * expert_weight_count(fields)
    return n_full * (common + full_layer_weight_count(fields)) \
        + n_lin * (common + linear_layer_weight_count(fields)) \
        + 2 * int(fields["vocab_size"]) * h + h


def kv_bytes_per_token(fields: dict, itemsize: int = 2) -> int:
    """K and V rows in the full-attention layers only."""
    _, _, nkv, hd = _attn_dims(fields)
    return _layers(fields)[1] * 2 * nkv * hd * itemsize


def state_bytes(fields: dict) -> int:
    """One request's float32 state in ONE linear layer: a matrix a value
    head."""
    _, nv, dk, dv, _ = _lin_dims(fields)
    return nv * dk * dv * 4


def slot_state_bytes(fields: dict, itemsize: int = 2) -> int:
    """What one request holds in ONE linear layer: the state and the conv's
    tail of ``K - 1`` rows."""
    K = _lin_dims(fields)[4]
    return state_bytes(fields) + (K - 1) * conv_channels(fields) * itemsize


def state_step_bytes(fields: dict, live_slots: float,
                     itemsize: int = 2) -> float:
    """Bytes the recurrence of one decode step must move: every live
    slot's STATE read once and written once, in every linear layer.  The
    conv's tail is not in it: the conv moves that, before the recurrence."""
    return _layers(fields)[0] * live_slots * 2 * state_bytes(fields)


def expert_step_bytes(fields: dict, experts_touched: float,
                      itemsize: int = 2) -> float:
    """Bytes the held experts' product must read in one step: the three
    matrices of every held expert that got a token, over all layers."""
    return experts_touched * expert_weight_count(fields) * itemsize


def decode_step_bytes(fields: dict, valid_kv_tokens: float,
                      itemsize: int = 2, live_slots: float = 0.0,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must move: every weight but the embedding
    table (a step gathers a few of its rows) and but the held experts that
    got no token this step, the K/V rows the live requests hold in the
    full-attention layers, and the live slots' state and conv tail in the
    linear ones (read and written).  ``experts_touched``: held experts
    with a token, summed over the layers of one step (default: all)."""
    held = int(fields["num_hidden_layers"]) * int(fields["num_experts"])
    idle = held - (held if experts_touched is None else experts_touched)
    weights = param_count(fields) \
        - int(fields["vocab_size"]) * int(fields["hidden_size"]) \
        - idle * expert_weight_count(fields)
    return weights * itemsize \
        + valid_kv_tokens * kv_bytes_per_token(fields, itemsize) \
        + _layers(fields)[0] * live_slots * 2 \
        * slot_state_bytes(fields, itemsize)


def paged_decode_attention_flops(fields: dict, live_tokens: float) -> float:
    """FLOPs the full-attention layers' decode attention needs in one step
    for ``live_tokens`` cached positions over all slots: per query head
    and key 2 x hd for the score and 2 x hd for the value."""
    _, nq, _, hd = _attn_dims(fields)
    return _layers(fields)[1] * live_tokens * 4.0 * nq * hd


def paged_decode_attention_bytes(fields: dict, live_tokens: float,
                                 slots: float, itemsize: int = 2) -> float:
    """Bytes the same step must move: each live K and V row once, and per
    slot the heads' queries in and their outputs out (float32)."""
    _, nq, nkv, hd = _attn_dims(fields)
    return _layers(fields)[1] * (
        live_tokens * 2 * nkv * hd * itemsize
        + slots * nq * hd * (itemsize + 4))


def chunk_scan_flops(fields: dict, rows: float) -> float:
    """FLOPs the chunked scan needs for ``rows`` rows of one request in
    every linear layer, at sub-chunks of C = ``SCAN_CHUNK`` rows (2 FLOPs a
    multiply-add).  Per KEY head and sub-chunk the two C x C Gram matrices
    K K^T and Q K^T, 4 C^2 dk, which its value heads share.  Per VALUE
    head and sub-chunk (decay and beta are a value head's own): the
    inverse of the unit lower-triangular (I + A) by forward substitution
    against C right-hand sides, C^3; its products with beta V and
    beta G K, 2 C^2 (dv + dk); the intra-chunk output (Q K^T * D) U,
    2 C^2 dv; and the three products with the carried state (W S, Q S,
    K^T U), 6 C dk dv.  Sub-chunks are counted as rows / C, a fraction
    where the rows end inside one."""
    nk, nv, dk, dv, _ = _lin_dims(fields)
    C = SCAN_CHUNK
    per_key = 4 * C * C * dk
    per_value = C * C * (2 * dk + 4 * dv) + C ** 3 + 6 * C * dk * dv
    return _layers(fields)[0] * (rows / C) * (nk * per_key + nv * per_value)


def chunk_scan_bytes(fields: dict, rows: float, itemsize: int = 2) -> float:
    """Bytes the same scan must move: q, k (a key head), v in and o out (a
    value head) at the served dtype, the two float32 scalars a row and
    value head, and each value head's state read once and written once."""
    nk, nv, dk, dv, _ = _lin_dims(fields)
    per_key = rows * 2 * dk * itemsize
    per_value = rows * (2 * dv * itemsize + 8) + 2 * dk * dv * 4
    return _layers(fields)[0] * (nk * per_key + nv * per_value)
