"""Test substrate: 8 simulated CPU devices (SURVEY.md §7.1) — the twin of the
reference's gloo-on-2-CPU-ranks mode.  Must configure XLA before the backend
initializes, hence the env mutation at import time."""

import json
import os
import socket
import subprocess
import sys
import tempfile
from pathlib import Path

from distributed_training_sandbox_tpu.utils import use_cpu_devices

use_cpu_devices(8)

# Telemetry runs and profiler traces from in-process script invocations go
# to throwaway dirs, not ./runs and ./profiler_traces in the checkout
# (subprocess-spawning tests inherit this too).
os.environ.setdefault(
    "RESULTS_DIR", tempfile.mkdtemp(prefix="dts-telemetry-runs-"))
os.environ.setdefault(
    "TRACE_DIR", tempfile.mkdtemp(prefix="dts-profiler-traces-"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

REPO = Path(__file__).resolve().parent.parent


def pytest_collection_modifyitems(config, items):
    """Run the ``kernels`` tier last.  The Pallas interpret-mode tests are
    the most expensive single file in the suite (step-level fp8/fused-kernel
    parity plus profiled contract smokes); appending them keeps the fast
    suites' ordering — and their position inside a wall-clock CI budget —
    identical to what it was before the tier landed."""
    items.sort(key=lambda it: 1 if it.get_closest_marker("kernels") else 0)


@pytest.fixture(scope="session")
def mesh8():
    assert len(jax.devices()) == 8, "expected 8 simulated CPU devices"
    return Mesh(np.array(jax.devices()).reshape(8), ("dp",))


@pytest.fixture(scope="session")
def mesh2x4():
    return Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "tp"))


class TwoProcessHarness:
    """Shared substrate for the ``multiproc`` suite: spawn real OS
    worker processes — raw ``python -c`` workers joined through a local
    coordinator, or full ``dts-launch`` groups — with a hermetic env.
    The test process's 8-device ``XLA_FLAGS`` must not leak into
    children that pick their own device counts."""

    repo = REPO

    @staticmethod
    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    @staticmethod
    def scrubbed_env(extra=None) -> dict:
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                            "JAX_NUM_PROCESSES")}
        env.update(extra or {})
        return env

    def spawn_two(self, worker: str, port: int, timeout: float = 420):
        """Two ``python -c <worker>`` processes sharing one coordinator
        port; returns ``(procs, outs)`` after both exit (killed on
        timeout so a wedged pair cannot outlive the test)."""
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", worker, str(port), str(pid),
                 str(REPO)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=self.scrubbed_env())
            for pid in range(2)
        ]
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            outs.append(out)
        return procs, outs

    def launch(self, args, workdir, extra_env=None, timeout=420):
        """``dts-launch run <args>`` in a subprocess; telemetry lands
        under ``<workdir>/runs``.  The launcher sets each worker's
        device count itself, so only XLA_FLAGS is scrubbed."""
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env.update({"JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": str(REPO),
                    "RESULTS_DIR": str(Path(workdir) / "runs")})
        env.update(extra_env or {})
        cmd = [sys.executable, "-m",
               "distributed_training_sandbox_tpu.launch.cli",
               "run"] + args
        return subprocess.run(cmd, env=env, cwd=str(REPO),
                              timeout=timeout, capture_output=True,
                              text=True)

    @staticmethod
    def loss_log(ckpt_dir) -> list[str]:
        """Full-precision loss trajectory from the newest runstate
        sidecar — repr strings, so equality == bitwise equality."""
        side = sorted(Path(ckpt_dir).glob("runstate-*.json"),
                      key=lambda p: int(p.stem.split("-")[1]))
        if not side:
            return []
        return [repr(v) for v in
                json.loads(side[-1].read_text())["loss_log"]]


@pytest.fixture(scope="session")
def procs2():
    return TwoProcessHarness()
