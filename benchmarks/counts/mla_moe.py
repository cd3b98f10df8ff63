"""Operations and bytes the latent-attention + held-experts decoder
(``reference/mla_moe.py``) REQUIRES, from the configuration's ``fields``
alone.  The yardstick: kept with the benchmark so that no PR that claims a
gain can change it.  ``fields`` count what THIS CHIP holds: the key
``n_routed_experts`` is the routed experts held here, ``router_width`` the
experts of the whole layer.

At the published widths (hidden 7680, 128 heads, ranks 1536 / 512, head
dims 128 / 64 / 128, dense 18432, expert 2048, router 256, vocabulary
153,600), 1 leading dense layer + 4 expert layers of 8 held experts:
5.474 G parameters = 10.95 GB of bf16; a token caches 576 x 2 B = 1,152 B
a layer.
"""

from __future__ import annotations


def _dims(fields: dict):
    return (int(fields["hidden_size"]), int(fields["num_attention_heads"]),
            int(fields["qk_nope_head_dim"]), int(fields["qk_rope_head_dim"]),
            int(fields["v_head_dim"]), int(fields["q_lora_rank"]),
            int(fields["kv_lora_rank"]))


def _layers(fields: dict) -> tuple[int, int]:
    """(leading dense layers, expert layers)."""
    k = int(fields["first_k_dense_replace"])
    return k, int(fields["num_hidden_layers"]) - k


def attention_weight_count(fields: dict) -> int:
    """One layer's latent projections: ``w_dq``, ``w_uq``, ``w_dkv``,
    ``w_uk`` + ``w_uv``, ``wo``."""
    h, n, dn, dr, dv, rq, rkv = _dims(fields)
    return h * rq + rq * n * (dn + dr) + h * (rkv + dr) \
        + rkv * n * (dn + dv) + n * dv * h


def expert_weight_count(fields: dict) -> int:
    """One expert (routed or shared): three matrices of hidden x width."""
    return 3 * int(fields["hidden_size"]) * int(fields["moe_intermediate_size"])


def _layer_rest(fields: dict) -> int:
    """One layer's norms: four of hidden, one of each rank."""
    h, _, _, _, _, rq, rkv = _dims(fields)
    return 4 * h + rq + rkv


def param_count(fields: dict) -> int:
    h = int(fields["hidden_size"])
    n_dense, n_expert = _layers(fields)
    common = attention_weight_count(fields) + _layer_rest(fields)
    dense = common + 3 * h * int(fields["intermediate_size"])
    expert = common + h * int(fields["router_width"]) \
        + expert_weight_count(fields) * (int(fields["n_routed_experts"])
                                         + int(fields["n_shared_experts"]))
    return n_dense * dense + n_expert * expert \
        + 2 * int(fields["vocab_size"]) * h + h


def kv_bytes_per_token(fields: dict, itemsize: int = 2) -> int:
    """One latent row ``[c_kv | k_rope]`` a layer."""
    *_, dr, _, _, rkv = _dims(fields)
    return int(fields["num_hidden_layers"]) * (rkv + dr) * itemsize


def decode_step_bytes(fields: dict, valid_kv_tokens: float,
                      itemsize: int = 2,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must read: every weight but the embedding
    table (a step gathers a few of its rows) and but the held experts that
    got no token this step, plus the latent rows the batch's live requests
    hold.  ``experts_touched``: held experts with a token, summed over the
    expert layers of one step (default: all of them)."""
    h = int(fields["hidden_size"])
    _, n_expert = _layers(fields)
    held = n_expert * int(fields["n_routed_experts"])
    idle = held - (held if experts_touched is None else experts_touched)
    weights = param_count(fields) - int(fields["vocab_size"]) * h \
        - idle * expert_weight_count(fields)
    return weights * itemsize \
        + valid_kv_tokens * kv_bytes_per_token(fields, itemsize)


def latent_decode_attention_flops(fields: dict, live_tokens: float) -> float:
    """FLOPs the absorbed decode attention needs in every layer of one step
    for ``live_tokens`` cached positions over all slots: per head and key
    2 x (rank + rope) for the score and 2 x rank for the value."""
    _, n, _, dr, _, _, rkv = _dims(fields)
    return float(fields["num_hidden_layers"]) * live_tokens \
        * 2.0 * n * ((rkv + dr) + rkv)


def latent_decode_attention_bytes(fields: dict, live_tokens: float,
                                  slots: float, itemsize: int = 2) -> float:
    """Bytes the same step must move: each live row once, and per slot the
    heads' absorbed queries in and their ``o~`` out (float32)."""
    _, n, _, dr, _, _, rkv = _dims(fields)
    per_slot = n * (rkv + dr) * itemsize + n * rkv * 4
    return float(fields["num_hidden_layers"]) * (
        live_tokens * (rkv + dr) * itemsize + slots * per_slot)


def expert_step_bytes(fields: dict, experts_touched: float,
                      itemsize: int = 2) -> float:
    """Bytes the held experts' product must read in one step: the three
    matrices of every held expert that got a token, over all expert
    layers."""
    return experts_touched * expert_weight_count(fields) * itemsize
