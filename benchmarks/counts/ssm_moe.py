"""Operations and bytes the hybrid decoder of Mamba-2 state-space layers
and NoPE GQA attention layers over routed and shared experts
(``reference/ssm_moe.py``) REQUIRES, from the configuration's ``fields``
alone, whatever implements it.  The yardstick: kept with the benchmark so
that no PR that claims a gain can change it.  ``fields`` count what THIS
CHIP holds: the key ``num_local_experts`` is the routed experts held here,
``router_width`` the experts of the whole layer.

At the published widths (hidden 4096; attention 32 heads of 128 with 8 KV
heads; Mamba-2 layers of 128 heads of 64 with a state of 128, one B/C
group, a conv of width 4 with bias over 8,448 channels; experts of width
768, router 72, top-10, one shared expert of 1536; vocabulary 100,352,
tied), 10 layers = one period of nine Mamba-2 layers and one attention
layer, 18 held experts a layer: 3.264 G parameters = 6.53 GB of bf16; a
token caches 2 x 8 x 128 x 2 B = 4,096 B in the one attention layer and
nothing in the nine Mamba-2 ones, each of which holds per REQUEST a float32
state of 128 x 64 x 128 x 4 B = 4,194,304 B and a bf16 conv tail of
3 x 8,448 x 2 B = 50,688 B.
"""

from __future__ import annotations

#: rows of one block of the chunked (SSD) scan the counts below are stated
#: for: the published ``mamba_chunk_size``
SCAN_BLOCK = 256


def _layers(fields: dict) -> tuple[int, int]:
    """(Mamba-2 layers, attention layers) of the layers that are run."""
    kinds = list(fields["layer_types"])[:int(fields["num_hidden_layers"])]
    n_attn = sum(k == "attention" for k in kinds)
    return len(kinds) - n_attn, n_attn


def _attn_dims(fields: dict):
    h = int(fields["hidden_size"])
    nq = int(fields["num_attention_heads"])
    return h, nq, int(fields["num_key_value_heads"]), \
        int(fields.get("head_dim") or h // nq)


def _ssm_dims(fields: dict):
    """(heads, head dim, state dim, conv width)."""
    return (int(fields["mamba_n_heads"]), int(fields["mamba_d_head"]),
            int(fields["mamba_d_state"]), int(fields["mamba_d_conv"]))


def conv_channels(fields: dict) -> int:
    n, hd, ds, _ = _ssm_dims(fields)
    return n * hd + 2 * int(fields.get("mamba_n_groups", 1)) * ds


def attention_layer_weight_count(fields: dict) -> int:
    """q, k, v, o; no norm, no bias."""
    h, nq, nkv, hd = _attn_dims(fields)
    return h * hd * (2 * nq + 2 * nkv)


def mamba_layer_weight_count(fields: dict) -> int:
    """The in-projection (z | xBC | dt) and the out-projection; the conv's
    weights and bias; ``A_log``, ``dt_bias``, ``Dskip``; the gated norm."""
    h = int(fields["hidden_size"])
    n, hd, _, K = _ssm_dims(fields)
    d, C = n * hd, conv_channels(fields)
    return h * (d + C + n) + d * h + (K + 1) * C + 3 * n + d


def expert_weight_count(fields: dict) -> int:
    """One routed expert: three matrices of hidden x width."""
    return 3 * int(fields["hidden_size"]) * int(fields["intermediate_size"])


def moe_rest_weight_count(fields: dict) -> int:
    """What an expert layer holds beside its routed experts: the router
    and the shared expert; and the layer's two norms."""
    h = int(fields["hidden_size"])
    return h * int(fields["router_width"]) \
        + 3 * h * int(fields["shared_intermediate_size"]) + 2 * h


def param_count(fields: dict) -> int:
    h = int(fields["hidden_size"])
    n_ssm, n_attn = _layers(fields)
    common = moe_rest_weight_count(fields) \
        + int(fields["num_local_experts"]) * expert_weight_count(fields)
    return n_attn * (common + attention_layer_weight_count(fields)) \
        + n_ssm * (common + mamba_layer_weight_count(fields)) \
        + int(fields["vocab_size"]) * h + h          # tied: one matrix


def kv_bytes_per_token(fields: dict, itemsize: int = 2) -> int:
    """K and V rows in the attention layers only."""
    _, _, nkv, hd = _attn_dims(fields)
    return _layers(fields)[1] * 2 * nkv * hd * itemsize


def state_bytes(fields: dict) -> int:
    """One request's float32 state in ONE Mamba-2 layer."""
    n, hd, ds, _ = _ssm_dims(fields)
    return n * hd * ds * 4


def slot_state_bytes(fields: dict, itemsize: int = 2) -> int:
    """What one request holds in ONE Mamba-2 layer: the state and the
    conv's tail of ``K - 1`` rows."""
    K = _ssm_dims(fields)[3]
    return state_bytes(fields) + (K - 1) * conv_channels(fields) * itemsize


def state_step_bytes(fields: dict, live_slots: float,
                     itemsize: int = 2) -> float:
    """Bytes one decode step must move for the recurrent state: every live
    slot's state and conv tail read once and written once, in every
    Mamba-2 layer."""
    return _layers(fields)[0] * live_slots * 2 \
        * slot_state_bytes(fields, itemsize)


def expert_step_bytes(fields: dict, experts_touched: float,
                      itemsize: int = 2) -> float:
    """Bytes the held experts' product must read in one step: the three
    matrices of every held expert that got a token, over all layers."""
    return experts_touched * expert_weight_count(fields) * itemsize


def decode_step_bytes(fields: dict, valid_kv_tokens: float,
                      itemsize: int = 2, live_slots: float = 0.0,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must move: every weight (the tied matrix is
    read whole as the head; the few rows the embedding gathers are in it)
    but the held experts that got no token this step, the K/V rows the
    live requests hold in the attention layers, and the live slots' state
    and conv tail in the Mamba-2 ones (read and written).
    ``experts_touched``: held experts with a token, summed over the layers
    of one step (default: all)."""
    held = int(fields["num_hidden_layers"]) * int(fields["num_local_experts"])
    idle = held - (held if experts_touched is None else experts_touched)
    weights = param_count(fields) - idle * expert_weight_count(fields)
    return weights * itemsize \
        + valid_kv_tokens * kv_bytes_per_token(fields, itemsize) \
        + state_step_bytes(fields, live_slots, itemsize)


def chunk_scan_flops(fields: dict, rows: float) -> float:
    """FLOPs the chunked (SSD) scan needs for ``rows`` VALID rows of one
    request in every Mamba-2 layer, at blocks of L = ``SCAN_BLOCK`` rows
    (2 FLOPs a multiply-add).  Per block, ONCE for all heads (B and C are
    one group's): the L x L product C B^T, 2 L^2 ds.  Per block and head:
    the decay-weighted intra-block product ((C B^T) * L) X, 2 L^2 hd; the
    state's part of the output (C * G) S_0, 2 L ds hd; the block's part of
    the new state (B * (G_end / G))^T X, 2 L ds hd.  Blocks are counted as
    rows / L, a fraction where the rows end inside one; the elementwise
    decays (an exp an entry of L) are not counted."""
    n, hd, ds, _ = _ssm_dims(fields)
    L = SCAN_BLOCK
    per_block = 2 * L * L * ds + n * (2 * L * L * hd + 4 * L * ds * hd)
    return _layers(fields)[0] * (rows / L) * per_block


def chunk_scan_bytes(fields: dict, rows: float, itemsize: int = 2) -> float:
    """Bytes the same scan must move: a row's x, B and C in and its output
    out at the served dtype, the float32 ``dt`` a row and head, and the
    state (every head's) read once and written once."""
    n, hd, ds, _ = _ssm_dims(fields)
    per_row = (2 * n * hd + 2 * ds) * itemsize + 4 * n
    return _layers(fields)[0] * (rows * per_row + 2 * state_bytes(fields))
