"""``run.py --rehearse-cpu`` runs every cell's control flow and its
reference check at tiny widths (four virtual devices for the four-chip
cell), and without a TPU no result line and no device metric name is ever
printed."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BM["workloads"]]
METRICS = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]


def _runner(cell):
    row = next(w for w in BM["workloads"] if w["name"] == cell)
    cfg = next(c for c in BM["configs"] if c["name"] == row["config"])
    return json.loads((ROOT / cfg["file"]).read_text())["runner"]


#: the last cell of each runner
ONE_PER_RUNNER = list({_runner(c): c for c in CELLS}.values())


def _run(*args, timeout=300):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"           # this sandbox has no accelerator
    return subprocess.run([sys.executable, str(ROOT / "benchmarks/run.py"),
                           *args], capture_output=True, text=True,
                          timeout=timeout, cwd=str(ROOT), env=env)


def _no_result(out):
    text = out.stdout + out.stderr
    for line in out.stdout.splitlines():
        if line.lstrip().startswith("{"):
            assert "metrics" not in json.loads(line), line
    named = [m for m in METRICS if m in text]
    assert not named, f"a CPU run printed metric name(s) {named}"


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_cell_and_prints_counts_only(cell):
    out = _run("--workload", cell, "--rehearse-cpu")
    assert out.returncode == 0, out.stdout + out.stderr
    chips = next(w["chips"] for w in BM["workloads"] if w["name"] == cell)
    assert f"rehearsal on cpu x{chips}: cell={cell} " in out.stdout
    assert "failed=0 reference_ok=True compiles_in_window=0" in out.stdout
    _no_result(out)


@pytest.mark.parametrize("cell", ONE_PER_RUNNER)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_without_a_tpu_it_exits_2_and_prints_no_result(cell, trace):
    out = _run("--workload", cell, "--seed", "1", "--seconds", "1",
               "--trace", trace)
    assert out.returncode == 2, out.stdout + out.stderr
    assert out.stdout.strip() == ""
    assert "need" in out.stderr and "TPU" in out.stderr
    _no_result(out)


def test_a_probe_prints_distances_and_never_a_result_line():
    """The override reaches the run: a tolerance no distance can meet turns
    the check's verdict, and the engine argument is taken."""
    over = {"config": {"serve": {"engine": {"kv_quant": True}},
                       "check": {"gap_sigma_mean": -1.0}}}
    out = _run("--workload", "serve-chat", "--rehearse-cpu", "--probe",
               json.dumps(over))
    assert out.returncode == 3, out.stdout + out.stderr
    assert out.stdout.startswith("probe ")
    said = json.loads(out.stdout[len("probe "):])
    assert said["override"] == over
    assert said["check"]["ok"] is False and said["check"]["tokens_checked"] > 0
    _no_result(out)
