"""The kernels' in-place shares (``decode_inplace_share``,
``prefill_inplace_share`` and their ``_tput`` twins): readers of the
engine's own counters, which find nothing on a program without them.
CPU only: counts, no device metric."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness  # noqa: E402

COUNTERS = {"prefill": ("prefill_inplace_chunks", "prefill_chunks"),
            "decode": ("decode_inplace_steps", "decode_steps")}


@pytest.mark.parametrize("name,moves,kind", [
    ("prefill_inplace_share", "serve_tpot_p50_ms", "prefill"),
    ("prefill_inplace_share_tput", "serve_tokens_per_s", "prefill"),
    ("decode_inplace_share", "serve_tpot_p50_ms", "decode"),
    ("decode_inplace_share_tput", "serve_tokens_per_s", "decode"),
])
def test_inplace_share_readers_read_the_engines_counters(name, moves, kind):
    """The share is the launches of that program whose attention was the
    in-place kernel over all of them, tracing or not; a program without
    the counter (the parent of the PR that brought it), or a window
    without such a launch, gives nothing and does not raise."""
    reader = harness.find_module("layer_metrics", name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == ("kernels", "%",
                                                         moves)
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
    counter moving: the dense block's two serving cells, not the latent
    block's, whose prefill bypasses the kernel; the engine names the
    counter beside the one it is a share of."""
    from distributed_training_sandbox_tpu.serving import engine
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bm["per_layer"]}
    for name, cells in (("prefill_inplace_share", ["serve-chat"]),
                        ("prefill_inplace_share_tput", ["serve-doc-batch"])):
        e = entries[name]
        assert e["workloads"] == cells and e["layer"] == "kernels"
        assert (e["unit"], e["better"], e["source"]) == (
            "%", "higher", "program_counter")
        reader = harness.find_module("layer_metrics", name)
        assert e["moves"] == reader.MOVES
    src = Path(engine.__file__).read_text()
    assert src.count('self.stats["prefill_inplace_chunks"] +=') == \
        src.count('self.stats["prefill_chunks"] += 1') == 2
