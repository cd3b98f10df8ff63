"""Operations and bytes the hybrid decoder of gated delta-rule layers and
full-attention layers (``reference/gdn_hybrid.py``) REQUIRES, from the
configuration's ``fields`` alone.  The yardstick: kept with the benchmark
so that no PR that claims a gain can change it.

At the published widths (hidden 3840, MLP 11008, 30 heads of 128 with 30
KV heads, linear layers of 30 heads with key dim 96, value dim 192 and a
conv of width 4 over 11,520 channels, vocabulary 100,352), 12 layers =
three periods of three linear layers and one full-attention layer:
3.266 G parameters = 6.53 GB of bf16; a token caches 2 x 30 x 128 x 2 B =
15,360 B in each of the 3 full-attention layers (46,080 B) and nothing in
the 9 linear ones, each of which holds per REQUEST a float32 state of
30 x 96 x 192 x 4 B = 2,211,840 B and a bf16 conv tail of 3 x 11,520 x 2 B
= 69,120 B.
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
    nkv = int(fields.get("num_key_value_heads") or nq)
    return h, nq, nkv, int(fields.get("head_dim") or h // nq)


def _lin_dims(fields: dict):
    return (int(fields["linear_num_key_heads"]),
            int(fields["linear_key_head_dim"]),
            int(fields["linear_value_head_dim"]),
            int(fields["linear_conv_kernel_dim"]))


def conv_channels(fields: dict) -> int:
    n, dk, dv, _ = _lin_dims(fields)
    return n * (2 * dk + dv)


def full_layer_weight_count(fields: dict) -> int:
    """q, k, v, o, the two whole-projection norms."""
    h, nq, nkv, hd = _attn_dims(fields)
    return h * hd * (2 * nq + 2 * nkv) + hd * (nq + nkv)


def linear_layer_weight_count(fields: dict) -> int:
    """q, k, v, gate and out projections; the two per-head scalars'
    projections, ``A_log``, ``dt_bias``; the conv; the per-head norm."""
    h = int(fields["hidden_size"])
    n, dk, dv, K = _lin_dims(fields)
    return h * n * (2 * dk + 2 * dv) + n * dv * h + 2 * h * n + 2 * n \
        + K * conv_channels(fields) + dv


def param_count(fields: dict) -> int:
    h = int(fields["hidden_size"])
    n_lin, n_full = _layers(fields)
    common = 3 * h * int(fields["intermediate_size"]) + 2 * h
    return n_full * (common + full_layer_weight_count(fields)) \
        + n_lin * (common + linear_layer_weight_count(fields)) \
        + 2 * int(fields["vocab_size"]) * h + h


def kv_bytes_per_token(fields: dict, itemsize: int = 2) -> int:
    """K and V rows in the full-attention layers only."""
    _, _, nkv, hd = _attn_dims(fields)
    return _layers(fields)[1] * 2 * nkv * hd * itemsize


def slot_state_bytes(fields: dict, itemsize: int = 2) -> int:
    """One request's state in ONE linear layer: the float32 matrix of every
    head and the conv's tail of ``K - 1`` rows."""
    n, dk, dv, K = _lin_dims(fields)
    return n * dk * dv * 4 + (K - 1) * conv_channels(fields) * itemsize


def state_step_bytes(fields: dict, live_slots: float,
                     itemsize: int = 2) -> float:
    """Bytes one decode step must move for the recurrent state: every live
    slot's state and tail read once and written once, in every linear
    layer."""
    return _layers(fields)[0] * live_slots * 2 \
        * slot_state_bytes(fields, itemsize)


def decode_step_bytes(fields: dict, valid_kv_tokens: float,
                      itemsize: int = 2,
                      live_slots: float = 0.0) -> float:
    """Bytes one decode step must move: every weight but the embedding
    table (a step gathers a few of its rows), the K/V rows the live
    requests hold in the full-attention layers, and the live slots' state
    in the linear ones (read and written)."""
    weights = param_count(fields) \
        - int(fields["vocab_size"]) * int(fields["hidden_size"])
    return weights * itemsize \
        + valid_kv_tokens * kv_bytes_per_token(fields, itemsize) \
        + state_step_bytes(fields, live_slots, itemsize)


def chunk_scan_flops(fields: dict, rows: float) -> float:
    """FLOPs the chunked scan needs for ``rows`` rows of one request in
    every linear layer, at sub-chunks of C = ``SCAN_CHUNK`` rows.  Per head
    and sub-chunk (2 FLOPs a multiply-add): the two C x C Gram matrices
    K K^T and Q K^T, 4 C^2 dk; the inverse of the unit lower-triangular
    (I + A) by forward substitution against C right-hand sides, C^3; its
    products with beta V and beta G K, 2 C^2 (dv + dk); the intra-chunk
    output (Q K^T * D) U, 2 C^2 dv; and the three products with the
    carried state (W S, Q S, K^T U), 6 C dk dv.  Sub-chunks are counted as
    rows / C, a fraction where the rows end inside one."""
    n, dk, dv, _ = _lin_dims(fields)
    C = SCAN_CHUNK
    per_chunk = C * C * (6 * dk + 4 * dv) + C ** 3 + 6 * C * dk * dv
    return _layers(fields)[0] * n * (rows / C) * per_chunk


def chunk_scan_bytes(fields: dict, rows: float, itemsize: int = 2) -> float:
    """Bytes the same scan must move: q, k, v in and o out at the served
    dtype, the two float32 scalars a row and head, and each head's state
    read once and written once."""
    n, dk, dv, _ = _lin_dims(fields)
    per_head = rows * ((2 * dk + 2 * dv) * itemsize + 8) + 2 * dk * dv * 4
    return _layers(fields)[0] * n * per_head
