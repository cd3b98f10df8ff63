"""L4 launch layer: config-driven strategy launcher with run-id'd trace dirs.

TPU-native twin of the reference's Modal launcher library
(``modal_utils.py:21-246``) and of its local ``trun`` wrapper
(``DDP/training_utils/trun.py:16-25``).  The responsibilities transfer; the
substrate changes:

  * the reference builds a cloud container and execs ``torchrun
    --nproc_per_node=N`` inside it; here the "cluster" is a jax device mesh —
    real TPU chips, or ``--cpu-devices N`` simulated devices (the gloo-mode
    twin) — so the SPMD default is ONE Python process per host.  The
    torchrun contract itself is ``nprocs > 1``: the launcher stands up a
    local coordinator (``DTS_COORDINATOR``/``DTS_NUM_PROCESSES``/
    ``DTS_PROCESS_ID`` env, consumed by
    ``utils.mesh.auto_initialize_from_env``) and spawns N workers whose
    simulated devices join ONE global mesh — the
    ``torchrun --standalone --nproc_per_node=N`` twin
    (``modal_utils.py:115-119``).
  * the GPU spec string ``"A10G:2"`` (``modal_utils.get_gpu_count``,
    ``modal_utils.py:60-72``) becomes a device spec ``"tpu"`` / ``"tpu:4"`` /
    ``"cpu:8"``: platform[:count].
  * the trace Volume + ``modal volume get`` retrieval loop
    (``DDP/scripts/profile.sh:97-109``) becomes a local run-id'd trace
    directory (``TRACE_DIR/<run_id>``) plus `sync_traces` (copy to a
    destination, e.g. a mounted bucket or rsync staging dir) and a printed
    TensorBoard recipe (``modal_utils._print_completion_message`` twin).

Config schema (dict or JSON/YAML file — inline dicts are what the reference
uses in every per-dir ``modal_app.py``, e.g. ``zero/modal_app.py:9-17``):

    {"app":      {"name": "zero", "script_dir": "scripts"},
     "devices":  {"spec": "cpu:8", "timeout": 1800},
     "trace":    {"root": "./profiler_traces", "local_dir": "./traces"},
     "launcher": {"env": {...}, "args": [...]}}

Every key has a default; ``LaunchConfig()`` with no args launches on
whatever devices exist.
"""

from __future__ import annotations

import json
import os
import shlex
import shutil
import subprocess
import sys
from dataclasses import dataclass, field
from pathlib import Path

from ..utils.config import build_run_id

#: strategy name -> script filename under script_dir (the `--script zero2.py`
#: surface of `RUN_MODAL.md:7-28`; bare names and `.py` names both accepted).
STRATEGY_SCRIPTS = {
    "ddp": "ddp.py",
    "zero1": "zero1.py",
    "zero2": "zero2.py",
    "zero3": "zero3.py",
    "fsdp": "train_fsdp.py",
    "train_fsdp": "train_fsdp.py",
    "gpipe": "gpipe.py",
    "1f1b": "1f1b.py",
    "interleaved_1f1b": "interleaved_1f1b.py",
    "interleaved": "interleaved_1f1b.py",
    "precision": "precision_benchmark.py",
    "precision_benchmark": "precision_benchmark.py",
    "busbench": "busbench.py",
    "train_sp": "train_sp.py",
    "sp": "train_sp.py",
    "train_tp": "train_tp.py",
    "tp": "train_tp.py",
    "moe": "moe.py",
    "train_moe": "train_moe.py",
    "composable": "train_composable.py",
    "train_composable": "train_composable.py",
    "ddp_utilization": "ddp_utilization.py",
}
# (ops_demo / memory_waterline / analyze_results / moe_profile /
# zigzag_flops / make_ops_notebook are NOT
# registered: they don't speak the strategy CLI contract the launcher
# injects (--num-steps/--cpu-devices) — run them directly.)

_REPO_ROOT = Path(__file__).resolve().parents[2]


def parse_device_spec(spec: str) -> tuple[str, int | None]:
    """``"cpu:8"`` -> ("cpu", 8); ``"tpu"`` -> ("tpu", None) = all chips.
    Twin of ``modal_utils.get_gpu_count`` (``modal_utils.py:60-72``)."""
    if ":" not in spec:
        return spec, None
    platform, count_str = spec.split(":", 1)
    try:
        count = int(count_str)
    except ValueError as exc:
        raise ValueError(f"Invalid device spec {spec!r}. Expected "
                         f"'PLATFORM:COUNT'.") from exc
    if count < 1:
        raise ValueError("device count must be >= 1")
    return platform, count


@dataclass
class LaunchConfig:
    """Launcher-level knobs, twin of ``ModalConfig`` (``modal_utils.py:21-59``)
    minus the container-image concerns a TPU-VM doesn't have."""
    name: str = "dts"
    script_dir: str | os.PathLike = _REPO_ROOT / "scripts"
    script: str = "fsdp"
    device_spec: str = "tpu"
    #: worker processes per host — the ``torchrun --nproc_per_node=N``
    #: twin (``modal_utils.py:115-119``).  1 = the SPMD default (one
    #: process per host); N > 1 spawns a coordinator env (DTS_* vars) and
    #: N workers whose simulated devices form ONE global mesh.
    nprocs: int = 1
    timeout: float | None = 1800.0          # zero/modal_app.py:12
    trace_root: str | os.PathLike = "./profiler_traces"
    trace_output_dir: str | os.PathLike = "./traces"   # sync destination
    env: dict = field(default_factory=dict)
    extra_args: list = field(default_factory=list)
    #: elastic worker groups (nprocs > 1): on a worker death — process
    #: exit, SIGKILL, or heartbeat staleness past ``heartbeat_timeout``
    #: seconds — tear down the group, shrink to the largest power-of-two
    #: worker count the survivors can fill, and relaunch with --resume
    #: appended (up to ``group_restarts`` times).  The training script's
    #: own checkpoint flags (--checkpoint-dir/--checkpoint-every) ride
    #: in ``extra_args``.
    elastic: bool = False
    group_restarts: int = 1
    heartbeat_timeout: float = 10.0
    #: real-distributed mode (``--distributed``): workers run the
    #: cross-process bring-up barrier after ``jax.distributed``
    #: initialize, with ``bringup_timeout`` seconds budget for both the
    #: rendezvous and the barrier — a missing peer becomes a readable
    #: BringupTimeout / StepTimeoutError in the worker log instead of a
    #: group that hangs until the launch timeout.
    distributed: bool = False
    bringup_timeout: float = 120.0

    @classmethod
    def from_config(cls, config: dict | str | os.PathLike) -> "LaunchConfig":
        """Dict, or path to a JSON/YAML file with the schema in the module
        docstring."""
        if not isinstance(config, dict):
            text = Path(config).read_text()
            if str(config).endswith((".yaml", ".yml")):
                import yaml  # gated: baked into the image with jax
                config = yaml.safe_load(text)
            else:
                config = json.loads(text)
        app = config.get("app", {})
        devices = config.get("devices", {})
        trace = config.get("trace", {})
        launcher = config.get("launcher", {})
        kw = {}
        if "name" in app:
            kw["name"] = app["name"]
        if "script_dir" in app:
            kw["script_dir"] = app["script_dir"]
        if "training_script" in app:
            kw["script"] = app["training_script"]
        if "spec" in devices:
            kw["device_spec"] = devices["spec"]
        if "nprocs" in devices:
            kw["nprocs"] = int(devices["nprocs"])
        if "timeout" in devices:
            kw["timeout"] = devices["timeout"]
        if "root" in trace:
            kw["trace_root"] = trace["root"]
        if "local_dir" in trace:
            kw["trace_output_dir"] = trace["local_dir"]
        if "elastic" in devices:
            kw["elastic"] = bool(devices["elastic"])
        if "group_restarts" in devices:
            kw["group_restarts"] = int(devices["group_restarts"])
        if "heartbeat_timeout" in devices:
            kw["heartbeat_timeout"] = float(devices["heartbeat_timeout"])
        if "distributed" in devices:
            kw["distributed"] = bool(devices["distributed"])
        if "bringup_timeout" in devices:
            kw["bringup_timeout"] = float(devices["bringup_timeout"])
        kw["env"] = dict(launcher.get("env", {}))
        kw["extra_args"] = list(launcher.get("args", []))
        return cls(**kw)

    def resolve_script(self, script: str | None = None) -> Path:
        """Strategy name or filename -> script path, validated the way each
        ``modal_app.py`` local entrypoint validates ``--script``
        (``zero/modal_app.py:21-31``).

        The default script_dir is the source checkout's ``scripts/``; a
        wheel install doesn't ship it, so fall back to ``./scripts`` (run
        from a checkout) before erroring with a pointer to the config."""
        name = script or self.script
        fname = STRATEGY_SCRIPTS.get(name.removesuffix(".py"),
                                     name if name.endswith(".py")
                                     else name + ".py")
        for base in (Path(self.script_dir), Path.cwd() / "scripts"):
            path = base / fname
            if path.exists():
                return path
        known = ", ".join(sorted(set(STRATEGY_SCRIPTS)))
        raise FileNotFoundError(
            f"training script {fname} not found under {self.script_dir} or "
            f"./scripts — run from a source checkout or point "
            f"app.script_dir at the strategy scripts. Known strategies: "
            f"{known}")


@dataclass
class RunResult:
    run_id: str
    trace_dir: Path
    command: list[str]
    returncode: int


def build_launch_command(config: LaunchConfig, script: str | None = None,
                         extra_args: list | None = None) -> list[str]:
    """Twin of ``modal_utils.build_launch_command`` (``modal_utils.py:107-148``).
    The torchrun/accelerate/python trichotomy collapses: SPMD JAX wants ONE
    process per host, so the launcher is always ``sys.executable``; the
    device spec rides on ``--cpu-devices`` (simulated mesh) or the default
    TPU runtime (real chips)."""
    platform, count = parse_device_spec(config.device_spec)
    cmd = [sys.executable, str(config.resolve_script(script))]
    if platform == "cpu":
        cmd += ["--cpu-devices", str(count or 8)]
    elif platform in ("tpu", "auto"):
        if count is not None:
            # Chip subsetting needs runtime support the scripts don't have
            # (they build their mesh over every visible device); refuse
            # loudly rather than run on all chips while claiming `count`.
            raise ValueError(
                f"device spec {config.device_spec!r}: TPU chip subsetting "
                f"is not supported — use 'tpu' (all chips) or 'cpu:<n>'")
    else:
        raise ValueError(f"unsupported platform {platform!r} "
                         f"(expected tpu, cpu:<n>, or auto)")
    cmd += [str(a) for a in config.extra_args]
    if extra_args:
        cmd += [str(a) for a in extra_args]
    return cmd


def run_training(config: LaunchConfig, *, script: str | None = None,
                 run_name: str | None = None, num_steps: int | None = None,
                 num_epochs: int | None = None, extra_args: list | None = None,
                 dry_run: bool = False) -> RunResult:
    """Launch one strategy script with a run-id'd trace dir — the
    ``run_training`` + ``train()`` arg-mapping twin
    (``modal_utils.py:151-188`` and ``:211-241``).

    Env contract: ``TRACE_DIR=<trace_root>/<run_id>`` is exported to the
    child (the scripts' ``default_trace_dir`` reads it), so traces land in
    a per-run directory the way each Modal run lands in its own volume
    prefix (``DDP/modal_app.py:116-121``)."""
    combined = []
    if num_steps is not None:
        combined += ["--num-steps", str(num_steps)]
    if num_epochs is not None:
        combined += ["--num-epochs", str(num_epochs)]
    if extra_args:
        combined += list(extra_args)

    run_id = build_run_id(run_name)
    trace_dir = Path(config.trace_root) / run_id
    cmd = build_launch_command(config, script, combined)

    env = os.environ.copy()
    env["TRACE_DIR"] = str(trace_dir)
    # group id shared by every worker of this launch: each rank's
    # TelemetryRun stamps it into its manifest (extra.launch_group), and
    # scripts/fleet_timeline.py groups the per-rank run dirs by it
    env["DTS_LAUNCH_GROUP"] = f"{config.name}-{run_id}"
    env.update({k: str(v) for k, v in config.env.items()})

    nprocs = int(config.nprocs or 1)
    if config.distributed and nprocs < 2:
        raise ValueError("--distributed needs --nprocs >= 2 (one process "
                         "is not a process group)")
    print(f"[launch] {config.name}: {' '.join(cmd)}"
          + (f" (x{nprocs} processes)" if nprocs > 1 else ""))
    print(f"[launch] TRACE_DIR={trace_dir}")
    if dry_run:
        return RunResult(run_id, trace_dir, cmd, 0)
    trace_dir.mkdir(parents=True, exist_ok=True)
    if nprocs > 1 and config.elastic:
        returncode = run_elastic_group(config, cmd, env, trace_dir, nprocs)
    elif nprocs > 1:
        returncode = _run_multiprocess(config, cmd, env, trace_dir, nprocs)
    else:
        returncode = subprocess.run(cmd, env=env,
                                    timeout=config.timeout).returncode
    if returncode == 0:
        print_completion_message(config, run_id, script or config.script)
    else:
        print(f"[launch] FAILED (exit {returncode}): {' '.join(cmd)}",
              file=sys.stderr)
    return RunResult(run_id, trace_dir, cmd, returncode)


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _exit_code(raw: int) -> int:
    """Propagatable exit code: signal-killed children report negative
    codes — map -SIG to the shell convention 128+SIG so the launcher's
    own exit status says *which* signal, not a flattened 1."""
    return 128 - raw if raw < 0 else raw


def _die_with_parent():
    """preexec_fn: workers get SIGTERM when the coordinator process
    dies (Linux PR_SET_PDEATHSIG) — a crashed/killed launcher must not
    leave stragglers spinning in collectives.  Best-effort: on
    platforms without prctl the group-kill paths below still cover
    every exit the coordinator survives long enough to handle."""
    try:
        import ctypes
        import signal as _signal
        libc = ctypes.CDLL("libc.so.6", use_errno=True)
        libc.prctl(1, _signal.SIGTERM)   # 1 == PR_SET_PDEATHSIG
    except Exception:  # noqa: BLE001 - portability fallback
        pass


@dataclass
class GroupResult:
    """Outcome of one worker-group attempt: the propagatable exit code
    (first nonzero worker's, 128+SIG for signal deaths), which ranks
    failed, how long detection took from first poll of the dead worker
    (the bounded-interval contract of the failure detector), and any
    worker pids that survived teardown un-reaped (zombies — must be
    empty before the coordinator may shrink and relaunch)."""
    returncode: int
    failed_ranks: list
    detect_s: float | None = None
    unreaped: list = field(default_factory=list)


def _group_hit_addrinuse(trace_dir: Path, nprocs: int) -> bool:
    """Did any worker of the last attempt die on a coordinator-port
    collision?  The launcher bind-probes for a free port, but the probe
    socket closes before ``jax.distributed`` rebinds it — another
    process can race into the gap, and the worker-side in-place retry
    (``setup_distributed``) only cures TIME_WAIT, not a genuinely taken
    port.  Scanned from the worker logs: the failure happens inside the
    child."""
    for pid in range(nprocs):
        log = trace_dir / f"worker_{pid}.log"
        try:
            text = log.read_text()
        except OSError:
            continue
        if "EADDRINUSE" in text or "address already in use" in text.lower():
            return True
    return False


def _run_worker_group(config: LaunchConfig, cmd: list[str], env: dict,
                      trace_dir: Path, nprocs: int,
                      heartbeat_dir: Path | None = None) -> GroupResult:
    """Port-rotating wrapper over :func:`_run_worker_group_once`: pick a
    fresh ephemeral coordinator port (bind-probe, never hardcoded), run
    the group, and if the attempt died with EADDRINUSE in a worker log,
    rotate to a NEW port and retry — bounded, so a genuinely broken
    network surfaces instead of looping."""
    max_attempts = 3
    res = None
    for attempt in range(max_attempts):
        port = _free_port()
        coord = f"127.0.0.1:{port}"
        res = _run_worker_group_once(config, cmd, env, trace_dir, nprocs,
                                     heartbeat_dir=heartbeat_dir,
                                     coord=coord)
        if res.returncode and attempt < max_attempts - 1 \
                and _group_hit_addrinuse(trace_dir, nprocs):
            print(f"[launch] coordinator port {port} collided "
                  f"(EADDRINUSE in worker log); rotating to a fresh "
                  f"port [{attempt + 1}/{max_attempts - 1}]",
                  file=sys.stderr)
            continue
        break
    return res


def _run_worker_group_once(config: LaunchConfig, cmd: list[str], env: dict,
                           trace_dir: Path, nprocs: int,
                           heartbeat_dir: Path | None = None,
                           coord: str | None = None) -> GroupResult:
    """The torchrun contract: coordinator address + N worker processes,
    each joining one global mesh via the DTS_* env consumed in
    ``utils.mesh.auto_initialize_from_env``.  Requires a ``cpu:K`` device
    spec (K simulated devices per process → an N·K-device mesh); real
    multi-host TPU launches use one process per host with JAX's own
    topology discovery instead.

    Failure detection in the coordinator path: every worker is polled
    for process death AND — when ``heartbeat_dir`` is set — probed
    through :class:`~..resilience.elastic.HeartbeatMonitor`, so a rank
    that is alive-but-wedged (or SIGKILLed with a ``.dead`` breadcrumb)
    is detected within ``config.heartbeat_timeout`` seconds instead of
    the group hanging in collectives until the full launch timeout.
    On any worker failure the survivors are killed promptly; every exit
    path (timeout, exception, coordinator death via PDEATHSIG) reaps
    the group — stragglers cannot outlive the launch.

    Worker stdout/stderr stream to ``<trace_dir>/worker_<i>.log``;
    worker 0's log is echoed on completion (the rank-0-prints-the-report
    convention of every strategy script)."""
    platform, _ = parse_device_spec(config.device_spec)
    if platform != "cpu":
        raise ValueError(
            f"nprocs={nprocs} needs a 'cpu:<k>' device spec (got "
            f"{config.device_spec!r}) — multi-process TPU uses one "
            f"process per host with auto topology discovery")
    if coord is None:
        coord = f"127.0.0.1:{_free_port()}"
    base_env = {k: v for k, v in env.items()
                if k not in ("JAX_PLATFORMS", "JAX_NUM_PROCESSES")}
    # keep user XLA_FLAGS; strip only the host-device-count flag that
    # would conflict with the per-worker cpu:K spec.  shlex keeps flag
    # values containing spaces (quoted --xla_dump_to paths) intact —
    # str.split would shatter them into separate bogus tokens.
    if "XLA_FLAGS" in base_env:
        kept = [f for f in shlex.split(base_env["XLA_FLAGS"])
                if not f.startswith("--xla_force_host_platform_device_count")]
        if kept:
            base_env["XLA_FLAGS"] = shlex.join(kept)
        else:
            del base_env["XLA_FLAGS"]
    monitor = None
    if heartbeat_dir is not None:
        from ..resilience.elastic import HeartbeatMonitor
        heartbeat_dir = Path(heartbeat_dir)
        monitor = HeartbeatMonitor(heartbeat_dir, nprocs,
                                   timeout_s=config.heartbeat_timeout)
    procs, logs = [], []
    for pid in range(nprocs):
        wenv = {**base_env, "DTS_COORDINATOR": coord,
                "DTS_NUM_PROCESSES": str(nprocs),
                "DTS_PROCESS_ID": str(pid)}
        if heartbeat_dir is not None:
            wenv["DTS_HEARTBEAT_DIR"] = str(heartbeat_dir)
        if config.distributed:
            # real-distributed mode: bounded bring-up + cross-process
            # barrier in the worker (utils.mesh.auto_initialize_from_env)
            wenv["DTS_DISTRIBUTED"] = "1"
            wenv["DTS_BRINGUP_TIMEOUT"] = str(config.bringup_timeout)
        log = (trace_dir / f"worker_{pid}.log").open("w")
        logs.append(log)
        procs.append(subprocess.Popen(
            cmd, env=wenv, stdout=log, stderr=subprocess.STDOUT,
            preexec_fn=_die_with_parent if os.name == "posix" else None))
    import time as _time
    deadline = (_time.monotonic() + config.timeout
                if config.timeout else None)
    rc, failed, detect_s = 0, [], None
    t_start = _time.monotonic()
    try:
        # poll ALL workers: if one dies during bring-up the survivors
        # block in collectives until timeout — kill the group as soon
        # as any worker exits nonzero (or goes heartbeat-dead) instead
        # of waiting it out
        live = dict(enumerate(procs))
        while live:
            if deadline and _time.monotonic() > deadline:
                raise subprocess.TimeoutExpired(cmd, config.timeout)
            for pid in list(live):
                code = live[pid].poll()
                if code is None:
                    continue
                del live[pid]
                # signal-killed workers return NEGATIVE codes — any
                # nonzero (either sign) must fail the run, and the
                # FIRST failure's code is the one the launch reports
                if code != 0:
                    if not failed:
                        rc = _exit_code(code)
                        detect_s = _time.monotonic() - t_start
                    failed.append(pid)
                    live.clear()
                    break
            if live and monitor is not None:
                dead = [r for r in monitor.dead_workers() if r in live]
                if dead:
                    rc = 128 + 9   # treated as SIGKILLed
                    detect_s = _time.monotonic() - t_start
                    failed.extend(dead)
                    live.clear()
            if live:
                _time.sleep(0.1)
    except subprocess.TimeoutExpired:
        raise
    finally:
        # orphan cleanup on EVERY exit path (failure, timeout,
        # KeyboardInterrupt, coordinator unwinding): kill + reap, then
        # VERIFY the reap — a pid still visible as a zombie after
        # wait() means teardown lied, and the relaunch would inherit
        # its coordinator port and device slots
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()
        for log in logs:
            log.close()
        from ..resilience.faults import unreaped_workers
        unreaped = unreaped_workers(procs)
        if unreaped:
            print(f"[launch] ERROR: worker pid(s) {unreaped} not reaped "
                  f"after group teardown (zombie)", file=sys.stderr)
    w0 = trace_dir / "worker_0.log"
    if w0.exists():
        sys.stdout.write(w0.read_text())
    for pid, p in enumerate(procs):
        if p.returncode:
            print(f"[launch] worker {pid} exit {p.returncode} — see "
                  f"{trace_dir / f'worker_{pid}.log'}", file=sys.stderr)
    if unreaped and rc == 0:
        rc = 1
    return GroupResult(returncode=rc, failed_ranks=sorted(set(failed)),
                       detect_s=detect_s, unreaped=unreaped)


def _run_multiprocess(config: LaunchConfig, cmd: list[str], env: dict,
                      trace_dir: Path, nprocs: int) -> int:
    """Back-compat shim over :func:`_run_worker_group`."""
    return _run_worker_group(config, cmd, env, trace_dir, nprocs).returncode


def run_elastic_group(config: LaunchConfig, cmd: list[str], env: dict,
                      trace_dir: Path, nprocs: int) -> int:
    """The coordinator-side elastic loop: launch the worker group with
    heartbeat monitoring; on a worker death shrink to the largest
    power-of-two count the survivors can fill and relaunch with
    ``--resume`` appended (the workers' own resilience runtime reshards
    the latest RunState into the smaller mesh).  Gives up when the
    restart budget is spent or the world cannot shrink further."""
    from ..resilience.elastic import shrink_plan, WorkerLost
    world, attempt = nprocs, 0
    cmd = list(cmd)
    transitions: list[dict] = []
    env = dict(env)
    while True:
        hb_dir = Path(trace_dir) / f"heartbeats-{attempt}"
        if transitions:
            # survivors stamp the launcher-level shrink into their
            # checkpoint lineage (supervisor._stamped reads this)
            env["DTS_MESH_TRANSITIONS"] = json.dumps(transitions)
        res = _run_worker_group(config, cmd, env, Path(trace_dir), world,
                                heartbeat_dir=hb_dir)
        if res.returncode == 0:
            return 0
        if attempt >= config.group_restarts:
            print(f"[launch] elastic: restart budget "
                  f"({config.group_restarts}) exhausted", file=sys.stderr)
            return res.returncode
        if res.unreaped:
            # shrinking over a zombie would relaunch while the dead
            # worker still pins its pid table entry (and, on a real
            # host, its device slots) — refuse rather than stack a new
            # group on top of an un-torn-down one
            print(f"[launch] elastic: refusing to shrink — worker "
                  f"pid(s) {res.unreaped} not reaped", file=sys.stderr)
            return res.returncode
        lost = res.failed_ranks or [world - 1]
        try:
            if len(set(lost)) >= world:
                # the WHOLE group went heartbeat-dead — a group-wide
                # wedge (hung collective), not a named worker loss:
                # halve the world, the StepTimeoutError policy
                plan = shrink_plan(world, [], force_shrink=True)
            else:
                plan = shrink_plan(world, lost)
        except WorkerLost:
            print(f"[launch] elastic: no viable group below {world} "
                  f"workers", file=sys.stderr)
            return res.returncode
        detect = (f" (detected in {res.detect_s:.1f}s)"
                  if res.detect_s is not None else "")
        print(f"[launch] elastic: worker(s) {lost} lost{detect}; "
              f"relaunching {plan.old_world} -> {plan.new_world} "
              f"workers with --resume "
              f"[{attempt + 1}/{config.group_restarts}]")
        transitions.append({
            "attempt": attempt, "old_world": plan.old_world,
            "new_world": plan.new_world, "lost": sorted(set(lost)),
            "detect_s": res.detect_s,
        })
        world = plan.new_world
        if "--resume" not in cmd:
            cmd.append("--resume")
        attempt += 1


def sync_traces(config: LaunchConfig, run_id: str | None = None,
                dest: str | os.PathLike | None = None) -> Path:
    """Copy trace dirs to the retrieval destination — local twin of
    ``modal volume get <vol> / <dest> --force`` (``profile.sh:97-102``).
    ``run_id=None`` syncs every run under the trace root."""
    dest = Path(dest or config.trace_output_dir)
    root = Path(config.trace_root)
    if run_id:
        if not (root / run_id).is_dir():
            raise FileNotFoundError(f"no run {run_id!r} under {root}")
        src_dirs = [root / run_id]
    else:
        src_dirs = sorted(p for p in root.iterdir() if p.is_dir()) \
            if root.exists() else []
        if not src_dirs:
            print(f"[launch] nothing to sync under {root}")
    dest.mkdir(parents=True, exist_ok=True)
    for src in src_dirs:
        shutil.copytree(src, dest / src.name, dirs_exist_ok=True)
        print(f"[launch] synced {src} -> {dest / src.name}")
    return dest


def print_completion_message(config: LaunchConfig, run_id: str,
                             script: str) -> None:
    """``modal_utils._print_completion_message`` twin (``:249-260``)."""
    root = Path(config.trace_root)
    print(f"\n[launch] Training complete!\n"
          f"  Run ID: {run_id}\n"
          f"  Script: {script}\n"
          f"  Traces: {root / run_id}\n"
          f"View with:\n"
          f"  tensorboard --logdir {root / run_id}\n"
          f"(or open the .trace.json.gz under plugins/profile/ at "
          f"ui.perfetto.dev)")


def view_command(config: LaunchConfig, run_id: str | None = None,
                 port: int = 6006) -> list[str]:
    """The `view` leg of profile.sh (``:104-109``): returns the TensorBoard
    invocation (callers may exec it; the CLI prints it by default since the
    build environment is headless)."""
    logdir = Path(config.trace_root)
    if run_id:
        logdir = logdir / run_id
    return ["tensorboard", "--logdir", str(logdir), "--port", str(port)]
