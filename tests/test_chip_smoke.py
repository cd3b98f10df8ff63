"""What keeps a CPU run from ever looking like a chip run: the smoke's
backend gate and the compile-cache rule.  Subprocesses throughout — each
case needs its own environment before jax is imported, and none may touch
this suite's backend."""

import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO

SMOKE = REPO / "chip_smoke.py"


def run(cmd, *, cwd, timeout=120, **env):
    """``cmd`` in a subprocess whose JAX-related environment is exactly
    ``env``."""
    clean = {k: v for k, v in os.environ.items()
             if k not in ("JAX_PLATFORMS", "XLA_FLAGS",
                          "JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
    return subprocess.run([sys.executable, *cmd], cwd=cwd, env={**clean, **env},
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"}, {}],
                         ids=["forced-cpu", "no-chip-found"])
def test_smoke_refuses_a_cpu_backend(tmp_path, env):
    """No accelerator — hidden by JAX_PLATFORMS=cpu, or simply absent so
    that JAX warns and carries on on the CPU — is exit 2, a message that
    says why, and no result line."""
    r = run([str(SMOKE)], cwd=tmp_path, **env)
    assert r.returncode == 2, r.stdout[-2000:] + r.stderr[-2000:]
    assert "backend=cpu" in r.stdout
    assert "needs 'tpu'" in r.stderr and "--rehearse-cpu" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_alone_is_not_a_pass(tmp_path):
    """The script without the program beside it must fail too."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = run(["chip_smoke.py"], cwd=tmp_path, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "ModuleNotFoundError" in r.stderr
    assert '"ok"' not in r.stdout


SHOW_CACHE = """
import distributed_training_sandbox_tpu, jax
from jax._src import xla_bridge
show = lambda: print(jax.config.jax_compilation_cache_dir,
                     jax.config.jax_persistent_cache_min_compile_time_secs)
show()
distributed_training_sandbox_tpu.utils.use_cpu_devices(2)
show()
assert not xla_bridge._backends, "a backend came up without being asked"
"""


def cache_config(cwd, **env) -> list:
    """``[directory, write threshold]`` once the package is imported, and
    again after ``use_cpu_devices`` — neither may start a backend."""
    r = run(["-c", SHOW_CACHE], cwd=cwd, PYTHONPATH=str(REPO), **env)
    assert r.returncode == 0, r.stderr[-2000:]
    return [line.split() for line in r.stdout.strip().splitlines()[-2:]]


def test_compile_cache_rule(tmp_path):
    """Placed from outside: the program sets nothing, whatever the
    platform.  Not placed: one fixed git-ignored directory in the
    checkout, the same from any process and any cwd (the path is part of
    the cache key), keeping every program — until the platform is forced
    to CPU, by ``use_cpu_devices`` or by ``JAX_PLATFORMS``: then no cache,
    so test runs never fill the checkout."""
    outside = str(tmp_path / "placed")
    assert cache_config(tmp_path, JAX_COMPILATION_CACHE_DIR=outside) \
        == [[outside, "1.0"]] * 2
    fixed = [str(REPO / ".jax_cache"), "0.0"]
    for cwd in (tmp_path, REPO / "scripts"):
        on_import, forced_cpu = cache_config(cwd)
        assert on_import == fixed and forced_cpu[0] == "None"
    assert cache_config(tmp_path, JAX_PLATFORMS="cpu")[0] == ["None", "1.0"]
    if (REPO / ".git").exists():
        ignored = subprocess.run(
            ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=REPO)
        assert ignored.returncode == 0, ".jax_cache/ is not git-ignored"
