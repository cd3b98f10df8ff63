"""Distributed drivers over REAL processes (ISSUE 13 tentpole).

``dts-launch run --nprocs 2 --distributed`` spawns two OS workers that
join through ``jax.distributed`` (gloo CPU collectives) and build ONE
global mesh spanning both — then the existing strategy scripts run
unchanged through ``use_cpu_devices``'s env-contract bootstrap.  The
headline guarantees pinned here:

  * the 2-process ddp trajectory is BITWISE-identical to the same
    global mesh shape in a single process (repr-string equality on the
    full-precision loss log);
  * bring-up is BOUNDED: a missing peer surfaces as a readable
    :class:`BringupTimeout` naming the rendezvous, never a silent hang;
  * real shrink-to-survivors: ``kill_worker@N:k`` SIGKILLs a worker's
    OS process, the coordinator re-initializes at the survivor count,
    and the resumed losses match a clean small-world twin bitwise
    (slow tier — the chaos campaign's ``real-kill_worker`` cell is the
    same proof);
  * the chaos harness smoke cell (``real-bringup``) stays green and its
    report round-trips through ``chaos_report.json``.
"""

import json

import pytest

pytestmark = pytest.mark.multiproc

# same hyperparameters as the pinned single-process references
DDP_FLAGS = ["--", "--scale", "100", "--batch-size", "32",
             "--no-profile", "--sync-every", "2",
             "--checkpoint-every", "2"]


def test_distributed_ddp_bitwise_vs_single_process(procs2, tmp_path):
    """Two processes x 2 devices vs one process x 4 devices, same
    global mesh — the loss logs must agree, proving the per-process
    batch shards assemble into the same global batch.  Agreement is to
    a few float32 ulps, not bitwise: the gradient all-reduce sums the
    four device contributions pairwise-within-then-across processes
    under gloo and in one pass inside a single process, and float32
    addition does not associate.  A mis-assembled batch moves the loss
    in its second or third digit."""
    ra = procs2.launch(
        ["--script", "ddp", "--num-steps", "4", "--devices", "cpu:2",
         "--nprocs", "2", "--distributed",
         "--trace-root", str(tmp_path / "traceA")] + DDP_FLAGS +
        ["--checkpoint-dir", str(tmp_path / "ckA")],
        tmp_path / "A")
    assert ra.returncode == 0, ra.stdout[-3000:] + ra.stderr[-2000:]
    rb = procs2.launch(
        ["--script", "ddp", "--num-steps", "4", "--devices", "cpu:4",
         "--trace-root", str(tmp_path / "traceB")] + DDP_FLAGS +
        ["--checkpoint-dir", str(tmp_path / "ckB")],
        tmp_path / "B")
    assert rb.returncode == 0, rb.stdout[-3000:] + rb.stderr[-2000:]
    la = procs2.loss_log(tmp_path / "ckA")
    lb = procs2.loss_log(tmp_path / "ckB")
    assert len(la) == 4, (la, ra.stdout[-2000:])
    assert [float(v) for v in la] == pytest.approx(
        [float(v) for v in lb], rel=1e-6, abs=0), (la, lb)


BRINGUP_ORPHAN = r"""
import sys
port = sys.argv[1]
sys.path.insert(0, sys.argv[3])
from distributed_training_sandbox_tpu.utils import use_cpu_devices
use_cpu_devices(2)
from distributed_training_sandbox_tpu.utils.mesh import (
    BringupTimeout, setup_distributed)
try:
    # rank 1 of a two-process group whose coordinator never launches
    setup_distributed(f"127.0.0.1:{port}", num_processes=2,
                      process_id=int(sys.argv[2]), timeout_s=4)
except BringupTimeout as e:
    msg = str(e)
    assert "timed out" in msg and port in msg and "num_processes=2" in msg, msg
    print("BRINGUP_TIMEOUT_READABLE", flush=True)
    sys.exit(0)
print("UNEXPECTED_SUCCESS", flush=True)
sys.exit(1)
"""


def test_bringup_timeout_is_readable(procs2):
    """A worker whose coordinator never shows up gets a BringupTimeout
    naming the rendezvous (coordinator, world size, rank) within the
    budget — not an indefinite hang inside jax.distributed.initialize.
    (The coordinator side of a missing peer is an XLA-level fatal abort
    the launcher reaps; only the connect side can raise in-process.)"""
    import subprocess
    import sys
    p = subprocess.run(
        [sys.executable, "-c", BRINGUP_ORPHAN,
         str(procs2.free_port()), "1", str(procs2.repo)],
        env=procs2.scrubbed_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "BRINGUP_TIMEOUT_READABLE" in p.stdout, p.stdout + p.stderr


@pytest.mark.chaos
def test_chaos_smoke_real_bringup(tmp_path):
    """Tier-1 chaos smoke: the harness's 2-process ``real-bringup``
    cell runs green end-to-end and its report parses — so the campaign
    machinery itself cannot rot between full ``--real`` sweeps."""
    import scripts.chaos as chaos
    report = tmp_path / "chaos_report.json"
    rc = chaos.main(["--real", "--cells", "real-bringup",
                     "--report", str(report),
                     "--workdir", str(tmp_path / "work")])
    doc = json.loads(report.read_text())
    assert rc == 0, doc
    assert doc["schema"] == 1
    assert doc["summary"] == {"total": 1, "green": 1, "red": 0}, doc
    cell = doc["cells"][0]
    assert cell["cell"] == "real-bringup"
    assert cell["invariants"]["global_mesh_spans_processes"] is True


@pytest.mark.slow
def test_distributed_zero1_bitwise_vs_single_process(procs2, tmp_path):
    """Same bitwise twin for the zero1 driver: optimizer-state
    sharding's gather/scatter choreography must survive the process
    boundary with zero numeric drift."""
    flags = ["--", "--scale", "100", "--batch-size", "32",
             "--no-profile", "--checkpoint-every", "2"]
    ra = procs2.launch(
        ["--script", "zero1", "--num-steps", "4", "--devices", "cpu:2",
         "--nprocs", "2", "--distributed",
         "--trace-root", str(tmp_path / "traceA")] + flags +
        ["--checkpoint-dir", str(tmp_path / "ckA")],
        tmp_path / "A")
    assert ra.returncode == 0, ra.stdout[-3000:] + ra.stderr[-2000:]
    rb = procs2.launch(
        ["--script", "zero1", "--num-steps", "4", "--devices", "cpu:4",
         "--trace-root", str(tmp_path / "traceB")] + flags +
        ["--checkpoint-dir", str(tmp_path / "ckB")],
        tmp_path / "B")
    assert rb.returncode == 0, rb.stdout[-3000:] + rb.stderr[-2000:]
    # the zero A/B driver checkpoints each leg in its own subdir
    for leg in ("baseline", "sharded"):
        la = procs2.loss_log(tmp_path / "ckA" / leg)
        lb = procs2.loss_log(tmp_path / "ckB" / leg)
        assert len(la) == 4 and la == lb, (leg, la, lb)


@pytest.mark.slow
def test_distributed_fsdp_completes(procs2, tmp_path):
    """The fsdp driver brings up, trains and tears down cleanly across
    two processes (per-layer gathers + reduce-scatters over the
    process boundary; numerics pinned by the in-driver loss check)."""
    r = procs2.launch(
        ["--script", "fsdp", "--num-steps", "2", "--devices", "cpu:2",
         "--nprocs", "2", "--distributed",
         "--trace-root", str(tmp_path / "trace"),
         "--", "--batch-size", "8", "--no-profile",
         "--sync-every", "2"],
        tmp_path, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]


@pytest.mark.slow
def test_real_shrink_bitwise_resume(procs2, tmp_path):
    """kill_worker@4:1 SIGKILLs worker 1 mid-run; the launcher reaps
    it, tears the coordinator down, re-initializes at world 1 on a
    fresh port, and the survivor's stitched losses are bitwise-equal
    to a clean small-world twin resuming from the SAME step (the async
    save racing the SIGKILL decides which step that is)."""
    ra = procs2.launch(
        ["--script", "ddp", "--num-steps", "8", "--devices", "cpu:2",
         "--nprocs", "2", "--distributed", "--elastic",
         "--heartbeat-timeout", "5",
         "--trace-root", str(tmp_path / "traceA")] + DDP_FLAGS +
        ["--checkpoint-dir", str(tmp_path / "ckA"),
         "--inject-fault", "kill_worker@4:1"],
        tmp_path / "A", timeout=600)
    assert ra.returncode == 0, ra.stdout[-3000:] + ra.stderr[-2000:]
    assert "relaunching 2 -> 1" in ra.stdout, ra.stdout[-3000:]

    resumed = -1
    for log in (tmp_path / "traceA").glob("*/worker_0.log"):
        for ln in log.read_text().splitlines():
            if "resumed from step " in ln:
                resumed = int(ln.split("resumed from step ")[1].split()[0])
    assert resumed >= 1, "survivor never resumed from a checkpoint"

    # clean-small twin: leave a newest checkpoint at exactly `resumed`,
    # then resume single-process to step 8
    rb1 = procs2.launch(
        ["--script", "ddp", "--num-steps", str(resumed + 1),
         "--devices", "cpu:4",
         "--trace-root", str(tmp_path / "traceB1")] + DDP_FLAGS +
        ["--checkpoint-dir", str(tmp_path / "ckB")],
        tmp_path / "B")
    rb2 = procs2.launch(
        ["--script", "ddp", "--num-steps", "8", "--devices", "cpu:2",
         "--trace-root", str(tmp_path / "traceB2")] + DDP_FLAGS +
        ["--checkpoint-dir", str(tmp_path / "ckB"), "--resume"],
        tmp_path / "B")
    assert rb1.returncode == 0 and rb2.returncode == 0, (
        rb1.stdout[-2000:], rb2.stdout[-2000:])

    la = procs2.loss_log(tmp_path / "ckA")
    lb = procs2.loss_log(tmp_path / "ckB")
    assert len(la) == 8 and la == lb, (resumed, la, lb)

    # the launcher-level shrink is visible in the checkpoint lineage
    side = sorted((tmp_path / "ckA").glob("runstate-*.json"),
                  key=lambda p: int(p.stem.split("-")[1]))
    trans = (json.loads(side[-1].read_text())["lineage"]
             .get("mesh_transitions") or [])
    assert trans and trans[0].get("new_world") == 1, trans
