"""The harness is driven by data: a cell, a configuration, a traffic mix or
a per-layer metric is added by adding files and entries, editing no file
that is there.  And ``BENCHMARK.json`` keeps to its contract's limits."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bm():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _list(root):
    out = subprocess.run([sys.executable, str(root / "benchmarks/run.py"),
                          "--list"], capture_output=True, text=True,
                         timeout=120, cwd=str(root))
    assert out.returncode == 0, out.stderr
    return {r["cell"]: r for r in map(json.loads, out.stdout.splitlines())}


def test_list_shows_every_cell_with_its_metrics(bm):
    cells = _list(ROOT)
    assert set(cells) == {w["name"] for w in bm["workloads"]}
    for row in cells.values():
        assert row["end_to_end"][0] == "setup_s" and len(row["end_to_end"]) > 1
        assert row["per_layer"]


def test_adding_files_and_entries_adds_a_cell_and_a_metric(tmp_path, bm):
    """One config file, one workload file, one reader, three entries: the
    copy's ``run.py --list`` shows them, and no file that was there
    changed."""
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*")
              if p.is_file()}
    b = tmp_path / "benchmarks"
    cfg = json.loads((b / "configs/smollm3-3b-serve.json").read_text())
    cfg["why"] = "a new configuration"
    (b / "configs/new-model-serve.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "workloads/chat-poisson.json").read_text())
    mix["params"]["arrival"]["rate_per_s"] = 99.0
    (b / "workloads/chat-burst.json").write_text(json.dumps(mix))
    (b / "layer_metrics/queue_depth_max.py").write_text(
        'LAYER = "scheduler"\nUNIT = "requests"\n'
        'MOVES = "serve_tpot_p50_ms"\nRUNNERS = ("serve",)\n\n\n'
        'def read(ctx):\n'
        '    return max((d for _, d in ctx.counters["queue_depth"]),'
        ' default=None)\n')
    new = json.loads(json.dumps(bm))
    new["configs"].append({"name": "new-model-serve", "source": "paper",
                           "file": "benchmarks/configs/new-model-serve.json",
                           "reduced": [], "why": "a new configuration"})
    new["workloads"].append({"name": "new-chat-burst",
                             "config": "new-model-serve",
                             "traffic": "chat-burst", "chips": 1,
                             "why": "a new cell"})
    new["per_layer"].append({"name": "queue_depth_max", "unit": "requests",
                             "better": "lower", "source": "program_counter",
                             "layer": "scheduler",
                             "moves": "serve_tpot_p50_ms",
                             "workloads": ["new-chat-burst"]})
    for m in new["end_to_end"] + new["per_layer"]:
        if "serve-chat" in m.get("workloads", []):
            m["workloads"].append("new-chat-burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    cells = _list(tmp_path)
    row = cells["new-chat-burst"]
    assert row["config"] == "new-model-serve" and row["traffic"] == "chat-burst"
    assert "queue_depth_max" in row["per_layer"]
    assert "serve_tpot_p50_ms" in row["end_to_end"]
    assert "queue_depth_max" not in cells["serve-chat"]["per_layer"]
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_a_reader_that_disagrees_with_its_entry_is_refused(tmp_path, bm):
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bad = json.loads(json.dumps(bm))
    bad["per_layer"][0]["unit"] = "furlongs"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bad))
    with pytest.raises(harness.BenchmarkError, match="furlongs"):
        harness.load_metrics(bad, tmp_path / "benchmarks")


def test_an_unknown_cell_names_the_known_ones():
    with pytest.raises(harness.BenchmarkError, match="serve-chat"):
        harness.load_cell("no-such-cell")


# ------------------------------------------------ the contract's own limits

def test_top_level_keys_and_command(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51
    assert 1 <= len(bm["paths"]) <= 16
    assert len(bm["command"]) <= 32
    script = [w for w in bm["command"] if "/" in w]
    assert all(any(w.startswith(p + "/") for p in bm["paths"])
               and not w.startswith("/") and ".." not in w for w in script)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lines(bm):
    rows = bm["configs"] + bm["workloads"] + bm["end_to_end"] + bm["per_layer"]
    for r in rows:
        assert NAME.match(r["name"]), r["name"]
        for key in ("why", "layer", "source"):
            if key in r:
                assert 1 <= len(r[key]) <= 200 and "\n" not in r[key] \
                    and "\t" not in r[key], (r["name"], key)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for kind in ("configs", "workloads"):
        names = [r["name"] for r in bm[kind]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_entries_have_just_the_contracts_keys(bm):
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_cells_configs_and_metrics_hang_together(bm):
    cfgs = {c["name"]: c for c in bm["configs"]}
    cells = {w["name"] for w in bm["workloads"]}
    assert 2 <= len(cells) <= 24 and 1 <= len(cfgs) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bm["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in bm["workloads"]} == set(cfgs)
    assert len({c["file"] for c in cfgs.values()}) == len(cfgs)
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    assert four <= max(len(cells) // 4, 1)
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:
        mine = {n for n, m in e2e.items()
                if cell in m.get("workloads", cells)}
        assert len(mine) >= 2, cell                # setup_s and one other
        layer = [m for m in bm["per_layer"]
                 if cell in m.get("workloads", cells)]
        assert layer, cell
        # a per-layer metric is reported only where the metric it moves is
        assert all(m["moves"] in mine for m in layer), cell


def test_config_files_state_their_cut_and_keep_published_widths(bm):
    widths = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "vocab_size")
    for c in bm["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        assert f["runner"] in ("train", "serve")
        for k in widths:
            assert f["fields"][k] == f["published"][k], (c["name"], k)
        changed = {k for k, v in f["published"].items()
                   if k in f["fields"] and f["fields"][k] != v}
        assert changed == set(c["reduced"]), c["name"]
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])


def test_layers_are_spelled_one_way(bm):
    layers = {m["layer"] for m in bm["per_layer"]}
    assert layers == {"host runtime", "model step", "kernels",
                      "strategy / collectives", "scheduler", "device",
                      "load generator"}
