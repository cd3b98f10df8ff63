"""Shared decode/serving byte accounting + the pool capacity planner.

One home for the HBM-read formulas the decode roofline
(``scripts/decode_bench.py``) and the serving runtime both price steps
with — previously the bench owned them privately, so the serving pool's
capacity planner would have had to re-derive the same arithmetic and
drift.  The planner half answers the sizing question the paged pool
asks at startup: *how many KV pages fit the HBM budget after the
weights are resident?* — the serving twin of the training-side
``memory_plan.analytic_waterline`` ledger (serving has no optimizer
state or activation peak worth modeling; the waterline is weights +
pool + headroom).
"""

from __future__ import annotations

GB = 1024 ** 3


def kv_bytes_per_step(cfg, batch: int, s_max: int, kv_quant: bool) -> int:
    """HBM bytes the attention READS from the KV cache per decode step:
    batch × S_max × layers × n_kv × hd × 2 (K and V) × itemsize.  The
    cache is a static (B, S_max, ...) buffer, so every step reads the
    whole capacity (masked), not just the live prefix — the honest
    denominator.  int8 cache adds the f32 row scales (hd→4 bytes)."""
    elems = batch * s_max * cfg.num_hidden_layers \
        * cfg.num_key_value_heads * cfg.resolved_head_dim * 2
    if kv_quant:
        return elems + (elems // cfg.resolved_head_dim) * 4
    return elems * 2          # bf16


def weight_read_bytes(cfg, params, wb: int) -> int:
    """Weight bytes a decode STEP actually reads: the embedding table is
    only GATHERED (B rows) per step, so when a separate unembedding
    exists (int8 decode's ``unembed_q``, or an untied ``lm_head``) the
    embed bytes drop out of the per-step read.  Tied bf16 decode reads
    the table as the unembedding matmul, so it stays."""
    if "unembed_q" in params or "lm_head" in params:
        return wb - cfg.vocab_size * cfg.hidden_size * 2   # bf16 embed
    return wb


def page_bytes(cfg, page_size: int, *, kv_quant: bool = False,
               tp: int = 1) -> int:
    """Bytes ONE page occupies across every PAGED layer's pool(s):
    page_size × ``kv_pool.paged_layers`` (all of them, but for the gated
    delta-rule hybrid's linear layers) × the pool's own row bytes
    (``kv_pool.token_row_bytes``: for the dense block (n_kv/tp local
    heads) × hd × 2 × itemsize plus the f32 per-row scales of an int8
    pool; for the latent block its one padded row).  This is the unit the
    capacity planner divides the budget by."""
    from .kv_pool import paged_layers, token_row_bytes
    return page_size * paged_layers(cfg) \
        * token_row_bytes(cfg, kv_quant=kv_quant, tp=tp)


def serve_waterline_gb(cfg, n_pages: int, page_size: int, *,
                       weight_bytes: int = 0, kv_quant: bool = False,
                       tp: int = 1, draft_weight_bytes: int = 0,
                       draft_cfg=None, max_batch: int = 0) -> float:
    """Static serving HBM waterline: resident weights + the paged KV
    pool.  Decode-step activations are a few (B, 1, H) rows — noise next
    to these two, so they are the whole ledger (the serving counterpart
    of ``memory_plan.analytic_waterline``'s train-side terms).

    Speculative decoding adds two resident terms: the draft model's
    weights, and the draft's OWN paged pool — the draft pool mirrors the
    target's page table 1:1 (same ``n_pages``, same ``page_size``, the
    draft cfg's shallower layer stack), so its bytes scale with the same
    page count.  Prefix sharing adds nothing here: aliased pages are the
    same physical pages, refcounts are host-side metadata — the waterline
    is a function of pool CAPACITY, not of how requests share it.

    A block with state slots (``kv_pool.slot_state_bytes``) adds
    ``max_batch`` of them, whatever the pages hold."""
    from .kv_pool import slot_state_bytes
    pool = n_pages * page_bytes(cfg, page_size, kv_quant=kv_quant, tp=tp) \
        + max_batch * slot_state_bytes(cfg)
    if draft_cfg is not None:
        pool += n_pages * page_bytes(draft_cfg, page_size,
                                     kv_quant=kv_quant, tp=tp)
    return (weight_bytes + draft_weight_bytes + pool) / GB


def pool_capacity_pages(cfg, page_size: int, *, budget_gb: float,
                        weight_bytes: int = 0, kv_quant: bool = False,
                        tp: int = 1,
                        headroom_fraction: float = 0.10,
                        draft_weight_bytes: int = 0,
                        draft_cfg=None, max_batch: int = 0) -> int:
    """Pages that fit ``budget_gb`` once the weights are resident, with
    ``headroom_fraction`` of the budget held back for the decode step's
    working set and allocator slack — the pool-sizing inverse of
    :func:`serve_waterline_gb`.  Returns 0 when the weights alone
    exceed the usable budget (the caller should refuse to serve).

    With a draft model resident (speculative decoding) the draft's
    weights come off the top and each page's marginal cost is the
    target page PLUS its draft-pool twin, keeping the inverse exact:
    ``serve_waterline_gb(cfg, N, p, ..., draft_cfg=d)`` at the returned
    N stays within budget.  ``max_batch`` state slots, where the block
    has them, come off the top like the weights."""
    from .kv_pool import slot_state_bytes
    usable = budget_gb * GB * (1.0 - headroom_fraction) \
        - weight_bytes - draft_weight_bytes \
        - max_batch * slot_state_bytes(cfg)
    if usable <= 0:
        return 0
    per_page = page_bytes(cfg, page_size, kv_quant=kv_quant, tp=tp)
    if draft_cfg is not None:
        per_page += page_bytes(draft_cfg, page_size, kv_quant=kv_quant,
                               tp=tp)
    return int(usable // per_page)
