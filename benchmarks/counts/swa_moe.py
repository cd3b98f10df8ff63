"""Operations and bytes the decoder of sliding-window and full GQA attention
layers over dense and then expert layers (``reference/swa_moe.py``)
REQUIRES, from the configuration's ``fields`` alone.  The yardstick: kept
with the benchmark so that no PR that claims a gain can change it.
``fields`` count what THIS CHIP holds: the key ``num_experts`` is the routed
experts held here, ``router_width`` the experts of the whole layer.

At the published widths (hidden 3072; 48 query and 8 KV heads of 128, a
gate projection as wide as the query's; dense 12,288; experts of 3,072,
router 256 with a selection bias, top-4, one shared expert; vocabulary
200,192), 5 layers = 1 dense + 4 expert layers of 8 held experts, of which
layer 3 attends its whole context and the other four a window of 4,096:
2.68 G parameters = 5.36 GB of bf16; a token caches 2 x 8 x 128 x 2 B =
4,096 B a layer, in the full layer for as long as its request lives and in
a window layer until 4,096 tokens have followed it.

The attention counts are of what the mask REQUIRES: per (row, visible key)
pair and query head 4 x head_dim FLOPs (score and value), and each visible
cached row's K and V bytes once, whatever blocks a kernel walks and
whichever other heads' columns it multiplies and masks.  So a kernel's
share of these at the peak cannot pass 100%.
"""

from __future__ import annotations


def _attn_dims(fields: dict):
    h = int(fields["hidden_size"])
    nq = int(fields["num_attention_heads"])
    return h, nq, int(fields["num_key_value_heads"]), \
        int(fields.get("head_dim") or h // nq)


def layer_kinds(fields: dict) -> tuple[int, int]:
    """(window layers, full layers): layer i, 0-based, is full where
    ``(i + 1) % global_attn_every_n_layers == 0``."""
    n_full = int(fields["num_hidden_layers"]) \
        // int(fields["global_attn_every_n_layers"])
    return int(fields["num_hidden_layers"]) - n_full, n_full


def _mlp_layers(fields: dict) -> tuple[int, int]:
    """(dense layers, expert layers)."""
    k = int(fields["num_dense_layers"])
    return k, int(fields["num_hidden_layers"]) - k


def attention_weight_count(fields: dict) -> int:
    """One layer's ``wq``, ``wg``, ``wo`` (hidden x heads x head_dim each)
    and ``wk``, ``wv``."""
    h, nq, nkv, hd = _attn_dims(fields)
    return h * hd * (3 * nq + 2 * nkv)


def expert_weight_count(fields: dict) -> int:
    """One expert (routed or shared): three matrices of hidden x width."""
    return 3 * int(fields["hidden_size"]) * int(fields["moe_intermediate_size"])


def _layer_rest(fields: dict) -> int:
    """One layer's norms: four of hidden, two of a head."""
    h, _, _, hd = _attn_dims(fields)
    return 4 * h + 2 * hd


def dense_layer_weight_count(fields: dict) -> int:
    h = int(fields["hidden_size"])
    return attention_weight_count(fields) + _layer_rest(fields) \
        + 3 * h * int(fields["intermediate_size"])


def expert_layer_weight_count(fields: dict) -> int:
    """Attention, norms, the router with its bias, the held and the shared
    experts."""
    h, width = int(fields["hidden_size"]), int(fields["router_width"])
    return attention_weight_count(fields) + _layer_rest(fields) \
        + h * width + width + expert_weight_count(fields) * (
            int(fields["num_experts"]) + int(fields["num_shared_experts"]))


def param_count(fields: dict) -> int:
    h = int(fields["hidden_size"])
    n_dense, n_expert = _mlp_layers(fields)
    return n_dense * dense_layer_weight_count(fields) \
        + n_expert * expert_layer_weight_count(fields) \
        + 2 * int(fields["vocab_size"]) * h + h


def kv_row_bytes(fields: dict, itemsize: int = 2) -> int:
    """One token's K and V rows in one layer."""
    _, _, nkv, hd = _attn_dims(fields)
    return 2 * nkv * hd * itemsize


def kv_bytes_per_token(fields: dict, itemsize: int = 2,
                       kind: str | None = None) -> int:
    """What one token caches: in every layer (``kind`` None), or in the
    layers of one ``kind`` alone, ``"window"`` or ``"full"``.  A window
    layer's share is held only while the token lies inside the window."""
    n_window, n_full = layer_kinds(fields)
    layers = {None: n_window + n_full, "window": n_window,
              "full": n_full}[kind]
    return layers * kv_row_bytes(fields, itemsize)


def expert_step_bytes(fields: dict, experts_touched: float,
                      itemsize: int = 2) -> float:
    """Bytes the held experts' product must read in one step: the three
    matrices of every held expert that got a token, over all expert
    layers."""
    return experts_touched * expert_weight_count(fields) * itemsize


def decode_step_bytes(fields: dict, valid_kv_tokens: float,
                      itemsize: int = 2,
                      window_kv_tokens: float | None = None,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must read: every weight but the embedding
    table (a step gathers a few of its rows) and but the held experts that
    got no token this step, plus the cached rows the batch's live requests
    SEE: ``valid_kv_tokens`` in a full layer, ``window_kv_tokens`` (the sum
    over slots of ``min(len, sliding_window)``; default: no request has
    passed the window) in a window layer."""
    h = int(fields["hidden_size"])
    _, n_expert = _mlp_layers(fields)
    held = n_expert * int(fields["num_experts"])
    idle = held - (held if experts_touched is None else experts_touched)
    weights = param_count(fields) - int(fields["vocab_size"]) * h \
        - idle * expert_weight_count(fields)
    if window_kv_tokens is None:
        window_kv_tokens = valid_kv_tokens
    return weights * itemsize \
        + valid_kv_tokens * kv_bytes_per_token(fields, itemsize, "full") \
        + window_kv_tokens * kv_bytes_per_token(fields, itemsize, "window")


def _decode_attention(fields, layers, rows, slots, itemsize):
    _, nq, _, hd = _attn_dims(fields)
    flops = float(layers) * rows * 4.0 * nq * hd
    # each visible row's K and V once; a slot's queries in, outputs out
    nbytes = float(layers) * (rows * kv_row_bytes(fields, itemsize)
                              + slots * nq * hd * (itemsize + 4))
    return flops, nbytes


def window_decode_attention_flops(fields: dict, window_rows: float) -> float:
    """FLOPs the window layers' decode attention needs in one step for
    ``window_rows`` visible cached rows over all slots (the sum of
    ``min(len, sliding_window)``)."""
    return _decode_attention(fields, layer_kinds(fields)[0], window_rows,
                             0.0, 2)[0]


def window_decode_attention_bytes(fields: dict, window_rows: float,
                                  slots: float = 0.0,
                                  itemsize: int = 2) -> float:
    """Bytes the same step must move in the window layers."""
    return _decode_attention(fields, layer_kinds(fields)[0], window_rows,
                             slots, itemsize)[1]


def full_decode_attention_flops(fields: dict, live_tokens: float) -> float:
    """FLOPs the full layers' decode attention needs in one step for
    ``live_tokens`` cached rows over all slots (the sum of ``len``)."""
    return _decode_attention(fields, layer_kinds(fields)[1], live_tokens,
                             0.0, 2)[0]


def full_decode_attention_bytes(fields: dict, live_tokens: float,
                                slots: float = 0.0,
                                itemsize: int = 2) -> float:
    """Bytes the same step must move in the full layers."""
    return _decode_attention(fields, layer_kinds(fields)[1], live_tokens,
                             slots, itemsize)[1]


def window_prefill_attention_flops(fields: dict,
                                   visible_pairs: float) -> float:
    """FLOPs the window layers' attention needs for a prefill chunk whose
    valid rows see ``visible_pairs`` (row, key) pairs in ONE such layer
    (row t sees ``min(t + 1, sliding_window)`` keys)."""
    _, nq, _, hd = _attn_dims(fields)
    return float(layer_kinds(fields)[0]) * visible_pairs * 4.0 * nq * hd


def window_prefill_attention_bytes(fields: dict, visible_pairs: float,
                                   rows: float, itemsize: int = 2) -> float:
    """Bytes the same chunk of ``rows`` rows must move in the window
    layers, at least: the keys one row sees on average (the union over the
    rows is no smaller), K and V once, and the rows' queries in and outputs
    out."""
    _, nq, _, hd = _attn_dims(fields)
    return float(layer_kinds(fields)[0]) * (
        visible_pairs / max(rows, 1.0) * kv_row_bytes(fields, itemsize)
        + rows * nq * hd * (itemsize + 4))
