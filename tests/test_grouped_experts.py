"""The held experts' routed sum as a grouped product over sorted (row,
expert) pairs (``ops/grouped_experts.py``) against the masked (held
experts x all rows) einsums it replaced in ``mla_moe.expert_mlp``, kept
here as the plain form: the three blocks' cut-down expert layers, float32,
on the CPU, through the plan's XLA form (what the model runs off the chip)
and through the Pallas kernel in interpret mode (what it runs on one)."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from distributed_training_sandbox_tpu.models import mla_moe as M  # noqa: E402
from distributed_training_sandbox_tpu.ops import grouped_experts as G  # noqa: E402
from tests import serving_blocks  # noqa: E402

BLOCKS = ("mla_moe", "gdn_moe", "swa_moe")


def masked_sum(rows, w_held, layer):
    """Every row through every held expert, the routing weight (zero where
    the expert was not chosen) masking it: ``expert_mlp``'s routed part as
    it was before the grouped product."""
    g = jnp.einsum("th,ehf->etf", rows, layer["we_gate"])
    u = jnp.einsum("th,ehf->etf", rows, layer["we_up"])
    y = jnp.einsum("etf,efh->eth", jax.nn.silu(g) * u, layer["we_down"],
                   preferred_element_type=jnp.float32)
    return jnp.einsum("eth,te->th", y, w_held)


def _routing(case, rows, layer, cfg):
    """``(w_held, idx, valid)`` of a case; ``rows`` (T, H)."""
    T = rows.shape[0]
    bias = {"bias": layer["router_bias"]} if "router_bias" in layer else {}
    w_held, idx = M.route(rows, layer["w_router"], cfg, **bias)
    valid = jnp.ones((T,), jnp.bool_)
    if case == "decode_dead_slots":
        valid = jnp.arange(T) % 3 != 1
    elif case == "ragged_prefill":
        valid = jnp.arange(T) < T - 7
    elif case == "none_held":
        w_held = jnp.zeros_like(w_held)
    elif case == "one_expert":
        w_held = jnp.zeros_like(w_held).at[:, 2].set(
            0.25 + jnp.arange(T, dtype=jnp.float32) / T)
    return w_held, idx, valid


#: case -> rows; 37 rows are no multiple of the 16-row tile
ROWS = {"decode_dead_slots": 6, "ragged_prefill": 24, "none_held": 8,
        "one_expert": 20, "odd_rows": 37}


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("case", list(ROWS))
@pytest.mark.parametrize("block", BLOCKS)
def test_grouped_product_is_the_masked_product(block, case, form,
                                               monkeypatch):
    _, cfg, params = serving_blocks.make(block, seed=3, scale=2.0)
    li = max(i for i, lw in enumerate(params["layers"]) if "we_gate" in lw)
    layer = params["layers"][li]
    T = ROWS[case]
    rows = jax.random.normal(jax.random.key(11), (T, cfg.hidden_size))
    w_held, idx, valid = _routing(case, rows, layer, cfg)
    per_row = min(cfg.num_experts_per_tok, cfg.held_experts)
    with jax.default_matmul_precision("highest"):
        want = masked_sum(rows, w_held, layer) * valid[:, None]
        got = G.routed_sum(
            rows, w_held, layer["we_gate"], layer["we_up"],
            layer["we_down"], per_row=per_row, valid=valid,
            interpret=True if form == "kernel" else None)
    assert got.dtype == jnp.float32 and got.shape == rows.shape
    np.testing.assert_allclose(got, want, atol=2e-5)
    if case in ("none_held", "one_expert"):
        assert np.asarray(want).any() == (case == "one_expert")
    if form == "kernel":
        return
    # the layer: the same routed sum under the shared expert, and the
    # counters as ``moe_counts`` has always counted them
    monkeypatch.setattr(M, "route", lambda r, w, c, **kw: (w_held, idx))
    with jax.default_matmul_precision("highest"):
        m, counts = M.expert_mlp(rows[None], layer, cfg=cfg,
                                 valid=valid[None])
        shared, _ = M.expert_mlp(
            rows[None], {**layer, "we_gate": layer["we_gate"] * 0},
            cfg=cfg, valid=valid[None])
    np.testing.assert_allclose(m[0] - shared[0], want, atol=2e-5)
    hit = np.asarray(w_held > 0) & np.asarray(valid)[:, None]
    assert [int(c) for c in counts] == [
        int(valid.sum()) * cfg.num_experts_per_tok, int(hit.sum()),
        int(hit.any(0).sum()), 1]
    np.testing.assert_array_equal(counts,
                                  M.moe_counts(w_held, idx, valid, cfg))


def _random_routing(rng, T, E, per_row):
    w = np.zeros((T, E), np.float32)
    for t in range(T):
        chosen = rng.choice(E, size=rng.integers(0, per_row + 1),
                            replace=False)
        w[t, chosen] = rng.uniform(0.1, 1.0, size=len(chosen))
    return w


@pytest.mark.parametrize("T", [29, 300])
def test_visits_are_sorted_by_expert_and_every_pair_has_a_place(T):
    """The plan of a routing whose rows choose 0 to ``per_row`` held
    experts: group sizes count the hits, visits go up the experts, the
    rows of one expert are in row order through its visits, and the
    tile rows (so the kernel's selection matrix), the row list and the
    weights say the same."""
    rng = np.random.default_rng(5)
    E, per_row = 8, 4
    w = _random_routing(rng, T, E, per_row)
    w[:, 3] = 0.0                                   # an expert nobody chose
    if T > 128:
        w[:, 6] = rng.uniform(0.1, 1.0, size=T)     # one that spans tiles
        w[:, 7] = 0.0           # (a row still has at most per_row choices)
    valid = rng.uniform(size=T) < 0.8
    sizes, n, expert, count, slot, row, weight = jax.tree.map(
        np.asarray, G.plan_visits(jnp.asarray(w), jnp.asarray(valid),
                                  per_row))
    hit = (w > 0) & valid[:, None]
    tm, V = G.row_tile(T), G.max_visits(T, E, per_row)
    assert slot.shape == (V, 1, T) and row.shape == (V * tm,)
    select = slot == np.arange(tm)[None, :, None]   # as the kernel takes it
    np.testing.assert_array_equal(sizes, hit.sum(0))
    assert n == sum(-(-s // tm) for s in sizes) <= V
    assert not count[n:].any() and not select[n:].any()
    assert (np.diff(expert[:n]) >= 0).all() and 3 not in expert[:n]
    row, weight = row.reshape(V, tm), weight[:, :, 0]
    for e in range(E):
        mine = np.flatnonzero(expert[:n] == e)
        got = np.concatenate([row[v, :count[v]] for v in mine] or [[]])
        np.testing.assert_array_equal(got, np.flatnonzero(hit[:, e]))
        assert all(count[v] == tm for v in mine[:-1])
        for v in mine:
            c = count[v]
            np.testing.assert_array_equal(weight[v, :c], w[row[v, :c], e])
            assert not weight[v, c:].any()
            want = np.zeros((tm, T), bool)
            want[np.arange(c), row[v, :c]] = True
            np.testing.assert_array_equal(select[v], want)


def test_the_walk_visits_only_touched_experts():
    """The kernel's grid is ``n`` visits, each a row tile of one expert
    with a pair in it: two of eight experts touched by three decode rows
    are two grid steps, no pair is none, and an expert whose 200 pairs
    span two tiles is visited twice."""
    def visits(w_held, per_row):
        plan = G.plan_visits(jnp.asarray(w_held), None, per_row)
        n = int(plan.n)
        return ([int(e) for e in plan.expert[:n]],
                [int(c) for c in plan.count[:n]])

    w = np.zeros((3, 8), np.float32)
    w[0, 5] = w[2, 5] = w[1, 1] = 0.5
    assert visits(w, 4) == ([1, 5], [1, 2])
    assert visits(np.zeros((48, 8), np.float32), 4) == ([], [])
    w = np.zeros((256, 8), np.float32)
    w[:200, 6] = 1.0
    w[7, 2] = 1.0
    assert visits(w, 4) == ([2, 6, 6], [1, 128, 72])
    # the static list holds the most visits any routing can make
    assert G.max_visits(256, 8, 4) == 16 and G.max_visits(48, 8, 4) == 8
    assert G.max_visits(64, 32, 10) == 32 and G.max_visits(512, 8, 4) == 24


def test_a_chunk_of_more_rows_than_stay_resident_is_taken_in_pieces(
        monkeypatch):
    """``max_rows`` bounds what one kernel call keeps in VMEM; a larger
    chunk is the same sum, piece by piece."""
    rng = np.random.default_rng(9)
    T, E, H, F, per_row = 300, 4, 128, 128, 2
    w = jnp.asarray(_random_routing(rng, T, E, per_row))
    ks = jax.random.split(jax.random.key(2), 4)
    rows = jax.random.normal(ks[0], (T, H))
    layer = {"we_gate": jax.random.normal(ks[1], (E, H, F)) / 8,
             "we_up": jax.random.normal(ks[2], (E, H, F)) / 8,
             "we_down": jax.random.normal(ks[3], (E, F, H)) / 8}
    assert G.max_rows(7680) == 384 and G.max_rows(2048) >= 512
    monkeypatch.setattr(G, "max_rows", lambda hidden: 128)
    with jax.default_matmul_precision("highest"):
        got = G.routed_sum(rows, w, layer["we_gate"], layer["we_up"],
                           layer["we_down"], per_row=per_row,
                           interpret=True)
        want = masked_sum(rows, w, layer)
    np.testing.assert_allclose(got, want, atol=2e-5)


#: the three configurations' published widths: held experts, hidden,
#: expert width, experts a token; decode rows and a prefill chunk's
PUBLISHED = {
    "pangu-ultra-moe-ep32-serve": (8, 7680, 2048, 8, 64, 256),
    "qwen3-next-80b-ep16-l24-serve": (32, 2048, 512, 10, 64, 256),
    "trinity-large-ep32-l5-serve": (8, 3072, 3072, 4, 48, 512),
}


@pytest.mark.parametrize("config", list(PUBLISHED))
def test_grouped_product_lowers_for_tpu_at_published_widths(config):
    """The op at a decode step's and a prefill chunk's rows, bf16,
    lowered FOR a TPU on this host: one Mosaic call whose resident rows
    and matrix blocks the tile rules chose inside the kernel's scoped
    VMEM, and no product over (held experts x rows)."""
    E, H, F, k, decode_rows, chunk_rows = PUBLISHED[config]
    sd = jax.ShapeDtypeStruct
    for T in (decode_rows, chunk_rows):
        tm, tf = G.row_tile(T), G.width_block(H, F)
        assert T <= G.max_rows(H) and F % tf == 0 and tf % 128 == 0
        # rows and sum resident (two buffers each), three matrix blocks
        # twice, the two tile scratches, the selection matrix
        vmem = 2 * T * H * (2 + 4) + 2 * 3 * H * tf * 2 \
            + tm * H * (2 + 4) + tm * max(T, 128) * 4
        assert vmem <= G.VMEM_LIMIT_BYTES * 0.75, (config, T, tm, tf, vmem)
        text = jax.jit(lambda r, w, a, b, c: G.routed_sum(
            r, w, a, b, c, per_row=min(k, E), interpret=False)).trace(
            sd((T, H), jnp.bfloat16), sd((T, E), jnp.float32),
            sd((E, H, F), jnp.bfloat16), sd((E, H, F), jnp.bfloat16),
            sd((E, F, H), jnp.bfloat16)).lower(
            lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 1
        assert f"tensor<{E}x{T}x{F}" not in text
        assert f"tensor<{E}x{T}x{H}" not in text
