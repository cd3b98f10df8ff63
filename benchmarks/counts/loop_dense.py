"""Operations and bytes the looped dense decoder (``reference/loop_dense.py``)
REQUIRES, from the configuration's ``fields`` alone.  The yardstick: kept
with the benchmark so that no PR that claims a gain can change it.  They
count the work the equations require, whatever implements it.

At the published widths (hidden 2048; 16 query and 16 KV heads of 128;
SwiGLU of 5632; 48 layers run ``total_ut_steps`` = 4 times; untied
vocabulary 49,152): a layer holds 4 x 2048^2 of projections, 3 x 2048 x
5632 of MLP and 4 norms = 51,388,416; 48 layers 2,466,643,968; embedding
and head 2 x 100,663,296; the final norm 2,048; the exit gate 2,049:
2,667,974,657 parameters = 5.336 GB of bf16.  A token caches 2 x 16 x 128
x 2 B = 8,192 B in every (pass, layer): 192 caches, 1,572,864 B.  Every
pass of a decode step must read every layer's weights again (pass t+1
starts when pass t's last layer is done, and 4.93 GB do not stay on the
chip between them).
"""

from __future__ import annotations


def _dims(fields: dict):
    """(hidden, query heads, KV heads, head dim)."""
    h = int(fields["hidden_size"])
    nq = int(fields["num_attention_heads"])
    nkv = int(fields.get("num_key_value_heads") or nq)
    hd = int(fields.get("head_dim") or h // nq)
    return h, nq, nkv, hd


def passes(fields: dict) -> int:
    return int(fields["total_ut_steps"])


def caches(fields: dict) -> int:
    """K/V caches a token holds a row in: one a (pass, layer)."""
    return passes(fields) * int(fields["num_hidden_layers"])


def layer_matmul_weight_count(fields: dict) -> int:
    """q, k, v, o and the three MLP matrices of one layer."""
    h, nq, nkv, hd = _dims(fields)
    return h * hd * (2 * nq + 2 * nkv) \
        + 3 * h * int(fields["intermediate_size"])


def layer_weight_count(fields: dict) -> int:
    """One weight layer: its matrices and its four norms."""
    return layer_matmul_weight_count(fields) + 4 * int(fields["hidden_size"])


def head_weight_count(fields: dict) -> int:
    return int(fields["vocab_size"]) * int(fields["hidden_size"])


def param_count(fields: dict) -> int:
    """Layers, embedding, untied head, the final norm, the exit gate's
    weight and bias."""
    h = int(fields["hidden_size"])
    return int(fields["num_hidden_layers"]) * layer_weight_count(fields) \
        + 2 * head_weight_count(fields) + h + (h + 1)


def model_flops_per_token(fields: dict, seq_len: int) -> float:
    """Forward FLOPs a token: 2 a weight it is multiplied by, every layer
    once a PASS and the head once, and causal attention in every cache at
    the mean context ``seq_len / 2``."""
    _, nq, _, hd = _dims(fields)
    L = int(fields["num_hidden_layers"])
    return 2.0 * (passes(fields) * L * layer_matmul_weight_count(fields)
                  + head_weight_count(fields)) \
        + caches(fields) * 4.0 * nq * hd * seq_len / 2


def kv_bytes_per_token(fields: dict, itemsize: int = 2) -> int:
    """K and V rows of (KV heads, head dim) in every (pass, layer)."""
    _, _, nkv, hd = _dims(fields)
    return caches(fields) * 2 * nkv * hd * itemsize


def decode_step_weight_bytes(fields: dict, rows: float = 0.0,
                             itemsize: int = 2) -> float:
    """Weight bytes one decode step must read: every layer once a PASS,
    the head, the final norm and the gate once, and the embedding's rows of
    the step's tokens."""
    h = int(fields["hidden_size"])
    return (passes(fields) * int(fields["num_hidden_layers"])
            * layer_weight_count(fields)
            + head_weight_count(fields) + 2 * h + 1 + rows * h) * itemsize


def decode_step_bytes(fields: dict, valid_kv_tokens: float,
                      itemsize: int = 2, rows: float = 0.0) -> float:
    """Bytes one decode step must read: ``decode_step_weight_bytes`` plus
    the K/V rows the live requests hold, in every cache."""
    return decode_step_weight_bytes(fields, rows, itemsize) \
        + valid_kv_tokens * kv_bytes_per_token(fields, itemsize)


def attention_kernel_flops(fields: dict, seq_len: int, n_seqs: int) -> float:
    """Forward FLOPs causal attention needs for ``n_seqs`` windows in every
    cache: QK^T and PV, each 2 S^2 hd a head, halved by the causal mask."""
    _, nq, _, hd = _dims(fields)
    return float(caches(fields)) * n_seqs * nq \
        * 2 * 2 * seq_len * seq_len * hd * 0.5


def attention_kernel_bytes(fields: dict, seq_len: int, n_seqs: int,
                           itemsize: int = 2) -> float:
    """Bytes the same kernels must move: q in, k and v in, o out."""
    _, nq, nkv, hd = _dims(fields)
    return float(caches(fields)) * n_seqs * seq_len * hd * itemsize \
        * (2 * nq + 2 * nkv)


def paged_decode_attention_flops(fields: dict, live_tokens: float) -> float:
    """FLOPs the decode attention needs in one step for ``live_tokens``
    cached positions over all slots: per query head and key 2 x hd for the
    score and 2 x hd for the value, in every cache."""
    _, nq, _, hd = _dims(fields)
    return caches(fields) * live_tokens * 4.0 * nq * hd


def paged_decode_attention_bytes(fields: dict, live_tokens: float,
                                 slots: float, itemsize: int = 2) -> float:
    """Bytes the same step must move: each live K and V row once, and per
    slot the heads' queries in and their outputs out (float32)."""
    _, nq, nkv, hd = _dims(fields)
    return caches(fields) * (live_tokens * 2 * nkv * hd * itemsize
                             + slots * nq * hd * (itemsize + 4))
