"""Operations and bytes the dense GQA decoder (``reference/dense_gqa.py``)
REQUIRES, from the configuration's ``fields`` alone.  The yardstick: kept
with the benchmark so that no PR that claims a gain can change it.
Recomputed operations (remat, a kernel re-deriving its scores in the
backward) are never counted.

``model_flops_per_token`` is the program's ``utils/flops.py``
``get_model_flops_per_token`` copied (6N convention: forward + 2x backward,
causal attention discounted by a half, tied vocabulary head counted).
"""

from __future__ import annotations


def _dims(fields: dict):
    h = int(fields["hidden_size"])
    nq = int(fields["num_attention_heads"])
    nkv = int(fields.get("num_key_value_heads") or nq)
    hd = int(fields.get("head_dim") or h // nq)
    return h, nq, nkv, hd


def model_flops_per_token(fields: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one token of a ``seq_len`` window needs."""
    h, nq, nkv, hd = _dims(fields)
    inter = int(fields["intermediate_size"])
    layers = int(fields["num_hidden_layers"])
    vocab = int(fields["vocab_size"])
    q_proj = 2 * h * nq * hd
    kv_proj = 2 * 2 * h * nkv * hd
    o_proj = 2 * nq * hd * h
    attn_quadratic = 2 * 2 * nq * hd * seq_len * 0.5
    mlp = 3 * 2 * h * inter
    fwd = layers * (q_proj + kv_proj + o_proj + attn_quadratic + mlp) \
        + 2 * h * vocab
    return 3.0 * fwd


def proj_mlp_weight_count(fields: dict) -> int:
    """Parameters of q, k, v, o and the three MLP matrices, every layer."""
    h, nq, nkv, hd = _dims(fields)
    per_layer = h * hd * (2 * nq + 2 * nkv) \
        + 3 * h * int(fields["intermediate_size"])
    return int(fields["num_hidden_layers"]) * per_layer


def param_count(fields: dict) -> int:
    h = int(fields["hidden_size"])
    norms = 2 * h * int(fields["num_hidden_layers"])
    embed = int(fields["vocab_size"]) * h
    head = 0 if fields.get("tie_word_embeddings", True) else embed
    return proj_mlp_weight_count(fields) + norms + embed + head + h


def attention_kernel_flops(fields: dict, seq_len: int, n_seqs: int) -> float:
    """FLOPs causal attention needs for ``n_seqs`` windows in every layer,
    forward and backward: 2 matmuls forward (QK^T, PV) and 4 backward (dV,
    dP, dQ, dK), each 2·S²·hd per head, halved by the causal mask."""
    _, nq, _, hd = _dims(fields)
    per_head = 6 * 2 * seq_len * seq_len * hd * 0.5
    return float(fields["num_hidden_layers"]) * n_seqs * nq * per_head


def attention_kernel_bytes(fields: dict, seq_len: int, n_seqs: int,
                           itemsize: int = 2) -> float:
    """Bytes the same kernels must move: forward reads q, k, v and writes
    o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    _, nq, nkv, hd = _dims(fields)
    q = seq_len * nq * hd * itemsize
    kv = seq_len * nkv * hd * itemsize
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 4 * kv
    return float(fields["num_hidden_layers"]) * n_seqs * (fwd + bwd)


def kv_bytes_per_token(fields: dict, itemsize: int = 2) -> int:
    _, _, nkv, hd = _dims(fields)
    return 2 * int(fields["num_hidden_layers"]) * nkv * hd * itemsize


def decode_step_bytes(fields: dict, valid_kv_tokens: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode step must read: every weight once, plus the KV of
    the positions the batch's live requests actually hold."""
    return param_count(fields) * itemsize \
        + valid_kv_tokens * kv_bytes_per_token(fields, itemsize)
