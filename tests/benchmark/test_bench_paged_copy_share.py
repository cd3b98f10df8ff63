"""The paged decode kernel's share of live pages among the pages it copies
(``paged_copy_live_share_tput``): a reader of the engine's two counters,
which finds nothing on a program without them.  CPU only: counts, no device
metric."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness  # noqa: E402


@pytest.mark.parametrize("stats,want", [
    ({"paged_pages_live": 130, "paged_pages_copied": 200}, 65.0),
    ({"paged_pages_live": 57, "paged_pages_copied": 57}, 100.0),
    ({"paged_pages_live": 0, "paged_pages_copied": 0}, None),   # no step
    ({"decode_steps": 12, "decode_inplace_steps": 12}, None),   # the parent
    ({}, None),
])
def test_paged_copy_live_share_reads_the_engines_two_counters(stats, want):
    """100 x the pages that held a live position over the pages the paged
    decode kernel's schedule copied, of the window's deltas; nothing, and
    no error, from a program without the counters or a window without a
    decode step."""
    reader = harness.find_module("layer_metrics", "paged_copy_live_share_tput")
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.RUNNERS) == (
        "kernels", "%", "serve_tokens_per_s", ("serve",))
    got = reader.read(SimpleNamespace(trace=None, counters={"stats": stats}))
    assert got == want


def test_paged_copy_live_share_is_listed_where_small_calls_run_the_kernel():
    """The entry stands after every older one, in the three cells whose
    whole-context layers run the float paged kernel at a few hundred to a
    thousand positions a slot, and says what the module says; the engine
    counts both keys at one place, from ``pages_copied``."""
    from distributed_training_sandbox_tpu.serving import engine
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = "paged_copy_live_share_tput"
    cells = ["serve-loop-reasoning", "serve-cca-moe-longgen",
             "serve-hybrid-moe-longgen"]
    order = [m["name"] for m in bm["per_layer"]]
    assert order.index(name) > order.index("loop_exit_step_mean_tput")
    assert bm["per_layer"][order.index(name)] == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "serve_tokens_per_s", "workloads": cells}
    for cell in cells:
        assert name in {m.name for m in harness.load_cell(cell).per_layer}
    assert name not in {
        m.name for m in harness.load_cell("serve-mla-moe-longgen").per_layer}
    src = Path(engine.__file__).read_text()
    assert src.count('self.stats["paged_pages_live"] +=') \
        == src.count('self.stats["paged_pages_copied"] +=') == 1
    assert "pages_copied(seen, self.page_size)" in src
