"""Autotuner suite: knob-space determinism, analytic pruning vs a
recorded OOM wall, cost-model champion rediscovery on the fixture
priors, priors that come only from the paths a caller names, and
bitwise ``--plan`` replay through the zero driver.

Everything runs on the 8-device simulated CPU mesh; the only compiles
are the two tiny zero-driver replays in the bitwise test."""

import glob
import inspect
import json

import pytest

from distributed_training_sandbox_tpu.models import transformer as T
from distributed_training_sandbox_tpu.tuner import (
    KnobSpace, TunerCandidate, TunerCostModel, check_plan, load_plan,
    save_plan, tune)
from distributed_training_sandbox_tpu.tuner.search import prune_candidates

from conftest import REPO

pytestmark = pytest.mark.tuner

# knob-matrix rows taken on a v5e before the ledger existed: fixtures of
# the cost model's tests, not a record of this system's speed
PRIORS = sorted(glob.glob(
    str(REPO / "tests" / "fixtures" / "bench_priors" / "BENCH_*.json")))

# the v5e single-chip HBM capacity those rows ran against
CAPACITY_GB = 15.75


# ------------------------------------------------------------ stage 1

def test_knob_space_enumeration_deterministic():
    """Two independently constructed spaces enumerate identically, hash
    identically, and sample identically under the same seed — the
    provenance stamp a plan.json carries is reproducible."""
    s1, s2 = KnobSpace(), KnobSpace()
    assert s1.space_hash() == s2.space_hash()
    assert s1.enumerate(2) == s2.enumerate(2)
    assert (s1.sample(20, seed=7, per_device_batch=2)
            == s2.sample(20, seed=7, per_device_batch=2))
    assert (s1.sample(20, seed=8, per_device_batch=2)
            != s1.sample(20, seed=7, per_device_batch=2))
    # axes -> from_axes round-trip preserves identity
    assert KnobSpace.from_axes(s1.axes()).space_hash() == s1.space_hash()


def test_knob_space_respects_feasibility_rules():
    """Enumeration applies the step factories' own rules: accumulation
    divides the per-device batch, activation offload only with a
    named-save remat policy."""
    for pdb in (1, 2):
        for c in KnobSpace().enumerate(pdb):
            assert (pdb * c.batch_scale) % c.accum_steps == 0
            if c.offload == "opt_act":
                assert c.remat_policy in ("save_attn", "save_dots_q8")


# ------------------------------------------------------------ stage 2

# the fixtures' OOM wall: (remat, matmul, state, global batch at ws=1,
# compiler-reported needed GB) — every row actually OOMed a 15.75 GB chip
OOM_WALL = [
    ("save_dots_q8", "int8_bwd", "full", 4, 18.41),
    ("full", "int8_bwd", "int8", 16, 19.86),
    ("save_dots", "int8_bwd", "int8", 2, 18.20),
    ("save_dots_q8", "int8_bwd", "int8", 4, 16.82),
]


def test_prune_agrees_with_recorded_oom_verdicts():
    """Stage-2 analytic pruning rejects every candidate the recorded
    round actually OOMed on, pre-compile, and reports each rejection
    with its predicted GB."""
    cfg = T.SMOLLM3_3B_L8
    cands = [TunerCandidate(batch_scale=b, remat_policy=r,
                            matmul_precision=q, state_precision=s)
             for r, q, s, b, _ in OOM_WALL]
    survivors, pruned, _ = prune_candidates(
        cands, cfg, base_batch=1, seq=8192, ws=1,
        capacity_gb=CAPACITY_GB)
    assert survivors == [], \
        f"recorded OOMs survived: {[c.bench_name() for c in survivors]}"
    assert len(pruned) == len(OOM_WALL)
    for row in pruned:
        assert row["predicted_gb"] > CAPACITY_GB
        assert row["capacity_gb"] == CAPACITY_GB


def test_prune_without_capacity_keeps_everything():
    """No capacity (CPU sim, no --budget-gb): nothing prunes, but the
    per-candidate predictions still ride along for the plan record."""
    cands = KnobSpace().enumerate(2)[:8]
    survivors, pruned, preds = prune_candidates(
        cands, T.TINY_LM, base_batch=2, seq=32, ws=8, capacity_gb=None)
    assert survivors == cands and pruned == []
    assert all(preds[c] > 0 for c in cands)


# ------------------------------------------------------------ stage 3

def test_champion_rediscovered_in_top5_on_checked_in_priors():
    """The acceptance rediscovery: enumerate the full space at the
    flagship's operating point, prune against the real chip capacity,
    rank on the fixture priors — the hand-found champion
    (explicit_int8_bwd_s8_b4x) must sit in the predicted
    top-5, i.e. the tuner would have measured it."""
    cfg = T.SMOLLM3_3B_L8
    cost = TunerCostModel.from_artifacts(prior_paths=PRIORS)
    cands = KnobSpace().enumerate(2)
    survivors, pruned, _ = prune_candidates(
        cands, cfg, base_batch=2, seq=8192, ws=1,
        capacity_gb=CAPACITY_GB)
    assert pruned, "the OOM wall should prune part of the space"
    ranked = cost.rank(survivors, cfg, seq=8192, base_batch=2, ws=1)
    top5 = [pred["config"] for _, pred in ranked[:5]]
    assert "explicit_int8_bwd_s8_b4x" in top5, top5


def test_cost_model_hash_tracks_priors():
    """Two cost models over the same priors hash identically; different
    priors hash differently — the plan's provenance stamp is real."""
    a = TunerCostModel.from_artifacts(prior_paths=PRIORS)
    b = TunerCostModel.from_artifacts(prior_paths=PRIORS)
    assert a.hash() == b.hash()
    c = TunerCostModel.from_artifacts(prior_paths=PRIORS[:1])
    assert c.hash() != a.hash()


@pytest.fixture
def cwd_with_prior(tmp_path, monkeypatch):
    """A working directory that holds a prior file nobody named."""
    (tmp_path / "BENCH_x.json").write_text(json.dumps({"matrix": [
        {"config": "explicit_save_dots", "tflops_per_device": 999.0,
         "step_ms": 100.0, "batch": 2}]}))
    monkeypatch.chdir(tmp_path)
    return tmp_path


def test_from_artifacts_reads_no_prior_it_was_not_given(cwd_with_prior):
    """Priors are the paths a caller names: a cost model built with none
    holds none, whatever the working directory holds, and ranks and
    hashes as one built from an empty list."""
    cfg = T.SMOLLM3_3B_L8
    unnamed = TunerCostModel.from_artifacts()
    empty = TunerCostModel.from_artifacts(prior_paths=[])
    assert unnamed.priors == [] and unnamed.prior_paths == []
    assert unnamed.hash() == empty.hash()
    cands = KnobSpace().enumerate(2)[:40]

    def order(cost):
        return [pred["config"] for _, pred in cost.rank(
            cands, cfg, seq=8192, base_batch=2, ws=1)]
    assert order(unnamed) == order(empty)
    named = TunerCostModel.from_artifacts(
        prior_paths=[str(cwd_with_prior / "BENCH_x.json")])
    assert named.priors and named.hash() != empty.hash()


def test_tune_script_ignores_working_directory(cwd_with_prior,
                                               tmp_path_factory):
    """``scripts/tune.py`` started where a prior file lies writes the
    plan it writes from an empty directory: same ranking, same
    provenance hashes."""
    from scripts.tune import main as tune_main

    def plan_from(cwd):
        out = cwd / "plan.json"
        assert tune_main(["--model", "TINY_LM", "--cpu-devices", "8",
                          "--out", str(out)]) == 0
        return load_plan(str(out))
    here = plan_from(cwd_with_prior)
    elsewhere = plan_from(tmp_path_factory.mktemp("empty"))
    assert ([r["config"] for r in here["ranking"]]
            == [r["config"] for r in elsewhere["ranking"]])
    for key in ("cost_model_hash", "priors_hash", "knob_space_hash",
                "provenance"):
        assert here[key] == elsewhere[key]
    assert here["provenance"]["prior_paths"] == []


# ------------------------------------------------------- plan + replay


def test_throughput_objective_ranks_and_measures_nothing():
    """``tune`` with ``top_k`` left alone compiles and measures nothing:
    the chosen candidate is the predicted best, and the plan keeps the
    keys a plan written with a measuring stage had."""
    params = inspect.signature(tune).parameters
    assert "measure_fn" not in params and "num_steps" not in params
    space = KnobSpace(batch_scale=(2,), accum_steps=(1,),
                      remat_policy=("full",), matmul_precision=("bf16",),
                      state_precision=("full",), offload=("none",))
    doc = tune("TINY_LM", 32, 2, space=space)
    assert doc["compiles_spent"] == 0 and doc["measured"] == []
    assert doc["chosen"]["measured"] is None
    assert doc["chosen"]["config"] == doc["ranking"][0]["config"]


def test_plan_replay_is_bitwise_deterministic(tmp_path):
    """A plan chosen by the tuner replays exactly: two zero-driver runs
    under the same ``--plan`` produce bit-identical loss sequences on
    both the baseline and sharded legs."""
    space = KnobSpace(batch_scale=(2,), accum_steps=(1,),
                      remat_policy=("full",), matmul_precision=("bf16",),
                      state_precision=("full",), offload=("none",))
    doc = tune("TINY_LM", 32, 2, space=space, top_k=0)
    path = tmp_path / "plan.json"
    save_plan(doc, str(path))
    loaded = load_plan(str(path))
    assert loaded["chosen"]["knobs"]["batch_scale"] == 2

    from scripts._zero_driver import run_zero_ab
    args = ["--scale", "100", "--num-steps", "4", "--no-profile",
            "--plan", str(path)]
    r1 = run_zero_ab(1, args)
    r2 = run_zero_ab(1, args)
    assert r1["base_losses"] == r2["base_losses"]
    assert r1["shard_losses"] == r2["shard_losses"]


def test_check_plan_flags_drift():
    """The staleness gate: a plan whose recorded hashes match the
    current code is fresh; a drifted knob-space or cost-model hash is
    reported with a reason naming what moved."""
    space = KnobSpace()
    cost = TunerCostModel(priors=[])
    doc = {"objective": "throughput",
           "knob_space_hash": space.space_hash(),
           "cost_model_hash": cost.hash()}
    fresh = check_plan(doc, space=space, cost=cost)
    assert not fresh["stale"] and fresh["reasons"] == []
    drifted = check_plan({**doc, "knob_space_hash": "deadbeef"},
                         space=space, cost=cost)
    assert drifted["stale"]
    assert any("knob space" in r for r in drifted["reasons"])


def test_load_plan_rejects_wrong_schema(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"schema_version": 99,
                             "chosen": {"knobs": {}}}))
    with pytest.raises(ValueError, match="schema_version"):
        load_plan(str(p))
