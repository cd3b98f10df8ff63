"""The harness is driven by data: a cell, a configuration, a traffic mix or
a per-layer metric is added by adding files and entries, editing no file
that is there.  And ``BENCHMARK.json`` keeps to its contract's limits: the
structural checks take the benchmark's root as a fixture and run on the
tree and on a copy of it with a serving cell, a training cell and a reader
appended as data, so no test of this directory can come to need an edit
for a cell or a reader that a later PR adds."""

import json
import os
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
def tree_bm():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


# ------------------------------------- additions, as data and nothing else

#: what a later PR may append: a serving cell and a training cell, each an
#: accepted configuration under an accepted traffic file (``_appended``)
ADDED_CELLS = [("appended-serve-cell", "smollm3-3b-serve"),
               ("appended-train-cell", "smollm3-3b-fsdp4-train")]
ADDED_READER = "appended_rounds_tput"


def _appended(bm: dict, name: str, config: str) -> tuple[dict, str]:
    """The row of a new cell of ``config``, and the accepted cell whose
    metrics' lists it joins (the configuration's last): under the first
    traffic file of a cell of the same runner that no cell pairs with
    ``config`` yet, on its configuration's chips."""
    def runner(c):
        row = next(r for r in bm["configs"] if r["name"] == c)
        return json.loads((ROOT / row["file"]).read_text())["runner"]
    like = [w for w in bm["workloads"] if w["config"] == config][-1]
    paired = {w["traffic"] for w in bm["workloads"] if w["config"] == config}
    traffic = next(w["traffic"] for w in bm["workloads"]
                   if runner(w["config"]) == runner(config)
                   and w["traffic"] not in paired)
    return {"name": name, "config": config, "traffic": traffic,
            "chips": like["chips"],
            "why": "a cell a later PR appends"}, like["name"]


def _overlay(root: Path) -> None:
    """``root/benchmarks``: every file of the tree's through a link, and one
    reader more.  No file that is there is written."""
    b = root / "benchmarks"
    (b / "layer_metrics").mkdir(parents=True)
    for child in (ROOT / "benchmarks").iterdir():
        if child.name not in ("layer_metrics", "__pycache__"):
            (b / child.name).symlink_to(child)
    for f in (ROOT / "benchmarks/layer_metrics").glob("*.py"):
        (b / "layer_metrics" / f.name).symlink_to(f)
    (b / "layer_metrics" / f"{ADDED_READER}.py").write_text(
        'LAYER = "scheduler"\nUNIT = "rounds"\n'
        'MOVES = "serve_tokens_per_s"\nRUNNERS = ("serve",)\n\n\n'
        'def read(ctx):\n'
        '    return ctx.counters["stats"].get("rounds") or None\n')


def _with_additions(root: Path) -> Path:
    """A benchmark root whose ``BENCHMARK.json`` is the tree's with two
    cells appended to ``workloads`` and to the lists of every metric their
    configuration's accepted cell reports, and one entry appended at the
    end of ``per_layer``."""
    new = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, config in ADDED_CELLS:
        row, like = _appended(new, name, config)
        new["workloads"].append(row)
        for m in new["end_to_end"] + new["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
    new["per_layer"].append({
        "name": ADDED_READER, "unit": "rounds", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tokens_per_s", "workloads": [ADDED_CELLS[0][0]]})
    _overlay(root)
    (root / "BENCHMARK.json").write_text(json.dumps(new, indent=1))
    return root


@pytest.fixture(scope="module")
def with_additions(tmp_path_factory):
    return _with_additions(tmp_path_factory.mktemp("additions"))


@pytest.fixture(scope="module", params=["tree", "tree_with_additions"])
def root(request):
    """The benchmark's root: this tree, and the copy with additions."""
    return ROOT if request.param == "tree" \
        else request.getfixturevalue("with_additions")


@pytest.fixture(scope="module")
def bm(root):
    return harness.load_benchmark(root)


def test_appended_cells_and_a_reader_load_with_their_readers(with_additions):
    """The property itself: a serving cell, a training cell and a per-layer
    entry appended as data load, each cell with every reader of the
    accepted cell whose lists it joined (and the serving one with the new
    reader), and the accepted cells report what they did."""
    root = with_additions
    names = lambda cell, r: [m.name for m in  # noqa: E731
                             harness.load_cell(cell, r).per_layer]
    for name, config in ADDED_CELLS:
        row, like = _appended(harness.load_benchmark(ROOT), name, config)
        cell = harness.load_cell(name, root)
        assert (cell.config_name, cell.traffic_name, cell.chips) == (
            config, row["traffic"], row["chips"])
        want = names(like, ROOT)
        assert [n for n in names(name, root) if n != ADDED_READER] == want
        assert [m.name for m in cell.end_to_end] == [
            m.name for m in harness.load_cell(like, ROOT).end_to_end]
    assert names(ADDED_CELLS[0][0], root)[-1] == ADDED_READER
    assert ADDED_READER not in names(ADDED_CELLS[1][0], root)
    for w in harness.load_benchmark(ROOT)["workloads"]:
        assert names(w["name"], root) == names(w["name"], ROOT)


def _list(root):
    out = subprocess.run([sys.executable, str(root / "benchmarks/run.py"),
                          "--list"], capture_output=True, text=True,
                         timeout=120, cwd=str(root))
    assert out.returncode == 0, out.stderr
    return {r["cell"]: r for r in map(json.loads, out.stdout.splitlines())}


def test_list_shows_every_cell_with_its_metrics(root, bm):
    # ``run.py`` lists the tree it stands in; the copy's readers are links
    # into this tree, so the copy is listed in this process
    cells = _list(ROOT) if root == ROOT else {
        r["cell"]: r for r in harness.list_cells(root)}
    assert set(cells) == {w["name"] for w in bm["workloads"]}
    for row in cells.values():
        assert row["end_to_end"][0] == "setup_s" and len(row["end_to_end"]) > 1
        assert row["per_layer"]


def test_adding_files_and_entries_adds_a_cell_and_a_metric(tmp_path,
                                                           tree_bm):
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
    new = json.loads(json.dumps(tree_bm))
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


# ---------------------------------------- a new architecture, as files only

ONE_HOT_REFERENCE = '''"""A reference that disagrees with every program:
zeros everywhere but token 0."""
import jax.numpy as jnp


def logits_at(params, ids, positions, fields, block=1024):
    v = int(fields["vocab_size"])
    return jnp.zeros((positions.shape[0], v), jnp.float32).at[:, 0].set(1.0)
'''


def _add_cell(new, name, arch):
    new["configs"].append({"name": name, "source": "paper",
                           "file": f"benchmarks/configs/{name}.json",
                           "reduced": [], "why": f"architecture {arch}"})
    cell = name.replace("-serve", "-chat")
    new["workloads"].append({"name": cell, "config": name,
                             "traffic": "chat-poisson", "chips": 1,
                             "why": "a new cell"})
    for m in new["end_to_end"] + new["per_layer"]:
        if "serve-chat" in m.get("workloads", []):
            m["workloads"].append(cell)


@pytest.fixture(scope="module")
def new_arch(tmp_path_factory, tree_bm):
    """A copy of the benchmark that gains ONLY new files: a reference and a
    counts module under two new architecture names, configurations that
    name them, entries.  ``root/BENCHMARK.json`` lists the two sound new
    cells; ``root/faulty`` (the same ``benchmarks`` through a link) lists
    the two configurations whose ``architecture`` is wrong or missing."""
    root = tmp_path_factory.mktemp("new_arch")
    b = root / "benchmarks"
    shutil.copytree(ROOT / "benchmarks", b,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    shutil.copy(b / "reference/dense_gqa.py", b / "reference/new_block.py")
    (b / "reference/one_hot.py").write_text(ONE_HOT_REFERENCE)
    for arch in ("new_block", "one_hot"):
        shutil.copy(b / "counts/dense_gqa.py", b / f"counts/{arch}.py")
    cfg = json.loads((b / "configs/smollm3-3b-serve.json").read_text())
    sound, faulty = (json.loads(json.dumps(tree_bm)) for _ in range(2))
    for arch, name, new in (("new-block", "new-block-serve", sound),
                            ("one-hot", "one-hot-serve", sound),
                            ("no-such-block", "no-module-serve", faulty),
                            (None, "no-arch-serve", faulty)):
        named = {**cfg, "architecture": arch, "why": f"architecture {arch}"}
        if arch is None:
            del named["architecture"]
        (b / f"configs/{name}.json").write_text(json.dumps(named))
        _add_cell(new, name, arch)
    (root / "BENCHMARK.json").write_text(json.dumps(sound))
    (root / "faulty").mkdir()
    (root / "faulty/benchmarks").symlink_to(b, target_is_directory=True)
    (root / "faulty/BENCHMARK.json").write_text(json.dumps(faulty))
    return root, before


def _rehearse(root, cell):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))   # the program
    return subprocess.run(
        [sys.executable, str(root / "benchmarks/run.py"), "--workload", cell,
         "--rehearse-cpu"], capture_output=True, text=True, timeout=300,
        cwd=str(root), env=env)


def test_a_new_architecture_is_listed_with_the_shared_readers(new_arch):
    root, _ = new_arch
    cells = _list(root)
    assert cells["new-block-chat"]["architecture"] == "new-block"
    assert cells["one-hot-chat"]["architecture"] == "one-hot"
    assert cells["serve-chat"]["architecture"] == "dense_gqa"
    # the readers that count are shared, not copied: same entries, more cells
    assert cells["new-block-chat"]["per_layer"] \
        == cells["serve-chat"]["per_layer"]
    assert "decode_roofline" in cells["new-block-chat"]["per_layer"]
    counts = harness.cell_counts(harness.load_cell("new-block-chat", root),
                                 root / "benchmarks")
    assert Path(counts.__file__) == root / "benchmarks/counts/new_block.py"


def test_a_new_architectures_rehearsal_uses_its_own_reference(new_arch):
    root, _ = new_arch
    out = _rehearse(root, "new-block-chat")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "cell=new-block-chat" in out.stdout
    assert "reference_ok=True" in out.stdout
    check = json.loads(out.stdout.split("rehearsal check: ", 1)[1])
    assert check["reference"] == "benchmarks/reference/new_block.py"


def test_the_reference_lookup_is_live(new_arch):
    """The same program against a reference that puts token 0 first
    everywhere: ``reference_ok`` turns False, so the module the
    configuration names is the one that judges."""
    root, _ = new_arch
    out = _rehearse(root, "one-hot-chat")
    assert out.returncode == 1, out.stdout + out.stderr
    assert "failed=0 reference_ok=False" in out.stdout
    check = json.loads(out.stdout.split("rehearsal check: ", 1)[1])
    assert check["reference"] == "benchmarks/reference/one_hot.py"
    assert check["gap_sigma_max"] > 3.0 and check["tokens_checked"] > 0


def test_an_architecture_with_no_module_names_the_path(new_arch):
    root, _ = new_arch
    with pytest.raises(harness.BenchmarkError,
                       match=r"reference/no_such_block\.py"):
        harness.load_cell("no-module-chat", root / "faulty")
    with pytest.raises(harness.BenchmarkError, match="no_such_block"):
        harness.list_cells(root / "faulty")     # so --list fails too


def test_a_configuration_without_an_architecture_is_refused(new_arch):
    root, _ = new_arch
    with pytest.raises(harness.BenchmarkError,
                       match=r"no-arch-serve\.json names no \"architecture\""):
        harness.load_cell("no-arch-chat", root / "faulty")


def test_a_reference_without_what_its_runner_calls_names_the_file(new_arch):
    """``one_hot.py`` has ``logits_at`` and nothing else: enough for
    ``serve``, and for ``train`` a ``BenchmarkError`` before any work."""
    root, _ = new_arch
    cell = harness.load_cell("one-hot-chat", root)
    bench = root / "benchmarks"
    serve = harness.find_module("runners", "serve")
    train = harness.find_module("runners", "train")
    ref = harness.find_module("reference", cell.architecture, bench,
                              serve.REFERENCE_EXPORTS)
    assert Path(ref.__file__) == bench / "reference/one_hot.py"
    with pytest.raises(harness.BenchmarkError,
                       match=r"one_hot\.py has no 'loss'"):
        harness.find_module("reference", cell.architecture, bench,
                            train.REFERENCE_EXPORTS)


def test_the_new_architecture_changed_no_file_that_was_there(new_arch):
    root, before = new_arch
    assert {p: p.read_bytes() for p in before} == before
    added = {str(p.relative_to(root / "benchmarks"))
             for p in (root / "benchmarks").rglob("*")
             if p.is_file() and p not in before
             and "__pycache__" not in p.parts}
    assert added == {
        "reference/new_block.py", "reference/one_hot.py",
        "counts/new_block.py", "counts/one_hot.py",
        "configs/new-block-serve.json", "configs/one-hot-serve.json",
        "configs/no-module-serve.json", "configs/no-arch-serve.json"}


def test_every_configuration_names_an_architecture_that_is_there(root, bm):
    for c in bm["configs"]:
        arch = json.loads((root / c["file"]).read_text())["architecture"]
        for kind in ("reference", "counts"):
            assert harness.module_path(kind, arch,
                                       root / "benchmarks").is_file()


def test_a_reader_that_disagrees_with_its_entry_is_refused(tmp_path,
                                                           tree_bm):
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bad = json.loads(json.dumps(tree_bm))
    bad["per_layer"][0]["unit"] = "furlongs"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bad))
    with pytest.raises(harness.BenchmarkError, match="furlongs"):
        harness.load_metrics(bad, tmp_path / "benchmarks")


def test_an_unknown_cell_names_the_known_ones():
    with pytest.raises(harness.BenchmarkError, match="serve-chat"):
        harness.load_cell("no-such-cell")


# ------------------------------------------------ the contract's own limits

def test_top_level_keys_and_command(root, bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51
    assert 1 <= len(bm["paths"]) <= 16
    assert len(bm["command"]) <= 32
    script = [w for w in bm["command"] if "/" in w]
    assert all(any(w.startswith(p + "/") for p in bm["paths"])
               and not w.startswith("/") and ".." not in w for w in script)
    assert len((root / "BENCHMARK.json").read_bytes()) <= 64 * 1024


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


def test_config_files_state_their_cut_and_keep_published_widths(root, bm):
    widths = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "vocab_size")
    for c in bm["configs"]:
        f = json.loads((root / c["file"]).read_text())
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        assert harness.module_path("runners", f["runner"],
                                   root / "benchmarks").is_file()
        for k in widths:
            assert f["fields"][k] == f["published"][k], (c["name"], k)
        changed = {k for k, v in f["published"].items()
                   if k in f["fields"] and f["fields"][k] != v}
        assert changed == set(c["reduced"]), c["name"]
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])


def test_layers_are_spelled_one_way(bm):
    """A metric's ``layer`` is a row of ``PERF.md``'s table of layers (the
    table headed ``| layer |`` in its section 3, and no other table or
    section), letter for letter; the seven accepted names stay.  A PR that
    brings a layer adds its row there."""
    layers = {m["layer"] for m in bm["per_layer"]}
    assert layers >= {"host runtime", "model step", "kernels",
                      "strategy / collectives", "scheduler", "device",
                      "load generator"}
    perf = (ROOT / "PERF.md").read_text().splitlines()
    start = next(i for i, line in enumerate(perf)
                 if line.startswith("## 3. Layers"))
    head = next(i for i in range(start, len(perf))
                if perf[i].startswith("| layer |"))
    end = next(i for i in range(head, len(perf))
               if not perf[i].startswith("|"))
    assert not any(line.startswith("## ") for line in perf[start + 1:end])
    rows = {line.split("|")[1].strip() for line in perf[head + 2:end]}
    assert layers <= rows, layers - rows
    # cells, metrics and configurations are rows of other tables: no layer
    assert not rows & {w["name"] for w in bm["workloads"]}
