"""The kernels' in-place shares (``decode_inplace_share``,
``prefill_inplace_share`` and their ``_tput`` twins, and the linear
layers' ``lin_step_inplace_share_tput``) and the head's share of the
prefill chunks (``prefill_head_share_tput``): readers of the engine's own
counters, which find nothing on a program without them.  CPU only: counts,
no device metric."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness  # noqa: E402

COUNTERS = {"prefill": ("prefill_inplace_chunks", "prefill_chunks"),
            "decode": ("decode_inplace_steps", "decode_steps"),
            "lin_step": ("lin_step_inplace_steps", "decode_steps"),
            "head": ("prefill_head_chunks", "prefill_chunks")}


@pytest.mark.parametrize("name,moves,kind,layer", [
    ("prefill_inplace_share", "serve_tpot_p50_ms", "prefill", "kernels"),
    ("prefill_inplace_share_tput", "serve_tokens_per_s", "prefill",
     "kernels"),
    ("decode_inplace_share", "serve_tpot_p50_ms", "decode", "kernels"),
    ("decode_inplace_share_tput", "serve_tokens_per_s", "decode", "kernels"),
    ("lin_step_inplace_share_tput", "serve_tokens_per_s", "lin_step",
     "kernels"),
    ("prefill_head_share_tput", "serve_tokens_per_s", "head", "model step"),
])
def test_inplace_share_readers_read_the_engines_counters(name, moves, kind,
                                                         layer):
    """The share is the launches of that program whose attention (or
    recurrence) was the in-place kernel, or whose chunk ran the head, over
    all of them, tracing or not; a program without the counter (the parent
    of the PR that brought it, or a block with no linear layer), or a
    window without such a launch, gives nothing and does not raise."""
    reader = harness.find_module("layer_metrics", name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (layer, "%", moves)
    assert reader.RUNNERS == ("serve",)
    done, all_ = COUNTERS[kind]
    ctx = lambda stats: SimpleNamespace(trace=None,
                                        counters={"stats": stats})
    assert reader.read(ctx({done: 12, all_: 12})) == 100.0
    assert reader.read(ctx({done: 0, all_: 12})) == 0.0
    assert reader.read(ctx({done: 3, all_: 12})) == 25.0
    assert reader.read(ctx({all_: 12})) is None       # the parent's stats
    assert reader.read(ctx({done: 0, all_: 0})) is None
    assert reader.read(ctx({})) is None


def test_the_prefill_shares_are_listed_for_the_dense_serving_cells():
    """``BENCHMARK.json`` lists each share where the reader finds the
    counter moving: the dense block's two serving cells first, then the
    hybrid whose full-attention layers prefill through the same kernel;
    not the latent block's, whose prefill bypasses it; the engine names the
    counter beside the one it is a share of."""
    from distributed_training_sandbox_tpu.serving import engine
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bm["per_layer"]}
    for name, cells in (("prefill_inplace_share", ["serve-chat"]),
                        ("prefill_inplace_share_tput",
                         ["serve-doc-batch", "serve-hybrid-rollout"])):
        e = entries[name]
        assert e["workloads"][:len(cells)] == cells
        assert "serve-mla-moe-longgen" not in e["workloads"]
        assert e["layer"] == "kernels"
        assert (e["unit"], e["better"], e["source"]) == (
            "%", "higher", "program_counter")
        reader = harness.find_module("layer_metrics", name)
        assert e["moves"] == reader.MOVES
    src = Path(engine.__file__).read_text()
    assert src.count('self.stats["prefill_inplace_chunks"] +=') == \
        src.count('self.stats["prefill_chunks"] += 1') == 2


def test_the_two_shares_of_pr_46_are_listed_where_their_counter_moves():
    """``lin_step_inplace_share_tput`` in the three cells whose block has a
    linear mixer (the engine makes the counter nowhere else),
    ``prefill_head_share_tput`` in the six backlog cells; each entry says
    what its module says, and the engine counts each beside the counter it
    is a share of."""
    from distributed_training_sandbox_tpu.serving import engine
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bm["per_layer"]}
    lin = ["serve-hybrid-rollout", "serve-hybrid-moe-longgen",
           "serve-ssm-moe-sessions"]
    for name, better, cells in (
            ("lin_step_inplace_share_tput", "higher", lin),
            ("prefill_head_share_tput", "lower",
             ["serve-doc-batch", "serve-mla-moe-longgen", *lin[:2],
              "serve-swa-moe-mixedlen", lin[2]])):
        e, mod = entries[name], harness.find_module("layer_metrics", name)
        assert [c for c in e["workloads"] if c in cells] == cells
        assert (e["unit"], e["better"], e["source"], e["layer"],
                e["moves"]) == ("%", better, "program_counter", mod.LAYER,
                                mod.MOVES)
        for cell in cells:
            assert name in {m.name for m in harness.load_cell(cell).per_layer}
    for cell in ("serve-doc-batch", "serve-mla-moe-longgen",
                 "serve-swa-moe-mixedlen"):      # no linear layer
        assert "lin_step_inplace_share_tput" not in {
            m.name for m in harness.load_cell(cell).per_layer}
    src = Path(engine.__file__).read_text()
    assert src.count('self.stats["lin_step_inplace_steps"] += sync') == 1
    assert src.count('self.stats["prefill_head_chunks"] +=') == \
        src.count('self.stats["prefill_chunks"] += 1') == 2
