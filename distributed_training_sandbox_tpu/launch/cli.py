"""`dts-launch` — the one-command strategy launcher CLI.

Surface twin of ``modal run <dir>/modal_app.py --script X --run-name Y
--num-steps N`` (``RUN_MODAL.md:7-28``) plus the ``profile.sh
run|sync|view|all`` loop (``DDP/scripts/profile.sh:167-199``), collapsed
into subcommands of one entrypoint:

    dts-launch run  --script zero2 --run-name sweep1 --num-steps 20 \
                    --devices cpu:8 [-- extra script args...]
    dts-launch sync [--run-id ID] [--dest DIR]
    dts-launch view [--run-id ID] [--port 6006]
    dts-launch all  --script ddp ...      # run -> sync -> print view recipe
    dts-launch list                       # known strategies + past runs
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

from .launcher import (LaunchConfig, STRATEGY_SCRIPTS, run_training,
                       sync_traces, view_command)


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--config", type=str, default=None,
                   help="JSON/YAML launch config (modal_app config-dict twin)")
    p.add_argument("--trace-root", type=str, default=None)
    p.add_argument("--dest", type=str, default=None,
                   help="sync destination (profile.sh --dest twin)")


def _build_config(args) -> LaunchConfig:
    cfg = (LaunchConfig.from_config(args.config) if args.config
           else LaunchConfig())
    if getattr(args, "trace_root", None):
        cfg.trace_root = args.trace_root
    if getattr(args, "dest", None):
        cfg.trace_output_dir = args.dest
    if getattr(args, "devices", None):
        cfg.device_spec = args.devices
    if getattr(args, "nprocs", None):
        cfg.nprocs = args.nprocs
    if getattr(args, "elastic", None):
        cfg.elastic = True
    if getattr(args, "group_restarts", None) is not None:
        cfg.group_restarts = args.group_restarts
    if getattr(args, "heartbeat_timeout", None) is not None:
        cfg.heartbeat_timeout = args.heartbeat_timeout
    if getattr(args, "distributed", None):
        cfg.distributed = True
    if getattr(args, "bringup_timeout", None) is not None:
        cfg.bringup_timeout = args.bringup_timeout
    return cfg


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dts-launch", description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    run = sub.add_parser("run", help="launch one strategy script")
    allp = sub.add_parser("all", help="run -> sync -> print view recipe")
    for sp in (run, allp):
        sp.add_argument("--script", required=True,
                        help=f"strategy ({', '.join(sorted(set(STRATEGY_SCRIPTS)))}) "
                             f"or a script filename")
        sp.add_argument("--run-name", type=str, default=None)
        sp.add_argument("--num-steps", type=int, default=None)
        sp.add_argument("--num-epochs", type=int, default=None)
        sp.add_argument("--devices", type=str, default=None,
                        help='device spec: "tpu" (default) or "cpu:8"')
        sp.add_argument("--nprocs", type=int, default=None,
                        help="worker processes (torchrun --nproc_per_node"
                             " twin); needs a cpu:<k> device spec")
        sp.add_argument("--distributed", action="store_true", default=None,
                        help="with --nprocs N: real jax.distributed mode — "
                             "bounded bring-up with a cross-process "
                             "barrier, one global mesh spanning all "
                             "worker processes")
        sp.add_argument("--bringup-timeout", type=float, default=None,
                        help="--distributed: seconds allowed for "
                             "rendezvous + bring-up barrier before a "
                             "missing peer raises (default 120)")
        sp.add_argument("--elastic", action="store_true", default=None,
                        help="with --nprocs: on worker death, shrink to "
                             "the survivors and relaunch with --resume "
                             "(heartbeat-monitored worker group)")
        sp.add_argument("--group-restarts", type=int, default=None,
                        help="elastic: worker-group relaunch budget "
                             "(default 1)")
        sp.add_argument("--heartbeat-timeout", type=float, default=None,
                        help="elastic: seconds without a worker heartbeat "
                             "before it is declared dead (default 10)")
        sp.add_argument("--dry-run", action="store_true",
                        help="print the command + trace dir, don't execute")
        sp.add_argument("extra", nargs=argparse.REMAINDER,
                        help="args after -- go to the training script")
        _add_common(sp)

    sync = sub.add_parser("sync", help="copy traces to the retrieval dir")
    sync.add_argument("--run-id", type=str, default=None)
    _add_common(sync)

    view = sub.add_parser("view", help="print the TensorBoard invocation")
    view.add_argument("--run-id", type=str, default=None)
    view.add_argument("--port", type=int, default=6006)
    view.add_argument("--exec", action="store_true", dest="exec_tb",
                      help="exec tensorboard instead of printing the recipe")
    _add_common(view)

    lst = sub.add_parser("list", help="known strategies and past runs")
    _add_common(lst)

    # autotuner: `dts-launch tune ...` forwards everything after the
    # subcommand to scripts/tune.py (enumerate / prune / rank ->
    # plan.json; --check = the staleness gate)
    tune = sub.add_parser(
        "tune", add_help=False,
        help="autotune knobs -> plan.json (scripts/tune.py)")
    tune.add_argument("tune_args", nargs=argparse.REMAINDER,
                      help="args for scripts/tune.py (see its --help)")

    # virtual-clock fleet simulator: `dts-launch sim ...` forwards to
    # scripts/sim_bench.py (traffic sim / --smoke / --validate /
    # --variant policy ranking / --rank-knobs prerank)
    sim = sub.add_parser(
        "sim", add_help=False,
        help="virtual-clock fleet simulator (scripts/sim_bench.py)")
    sim.add_argument("sim_args", nargs=argparse.REMAINDER,
                     help="args for scripts/sim_bench.py (see its "
                          "--help)")
    return p


def _forward(script: str, argv: list) -> int:
    """Run a scripts/ entry point in-process, argv forwarded verbatim
    (incl. --help)."""
    import importlib.util
    path = Path(__file__).resolve().parents[2] / "scripts" / script
    spec = importlib.util.spec_from_file_location(
        f"_dts_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main([a for a in argv if a != "--"])


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv[:1] == ["tune"]:
        return _forward("tune.py", argv[1:])
    if argv[:1] == ["sim"]:
        return _forward("sim_bench.py", argv[1:])
    args = build_parser().parse_args(argv)
    cfg = _build_config(args)

    if args.command in ("run", "all"):
        extra = [a for a in (args.extra or []) if a != "--"]
        result = run_training(
            cfg, script=args.script, run_name=args.run_name,
            num_steps=args.num_steps, num_epochs=args.num_epochs,
            extra_args=extra, dry_run=args.dry_run)
        if args.command == "all" and not args.dry_run:
            sync_traces(cfg, result.run_id)
            print("[launch] view with: "
                  + " ".join(view_command(cfg, result.run_id)))
        return result.returncode

    if args.command == "sync":
        sync_traces(cfg, args.run_id)
        return 0

    if args.command == "view":
        cmd = view_command(cfg, args.run_id, args.port)
        if args.exec_tb:
            import subprocess
            return subprocess.call(cmd)
        print(" ".join(cmd))
        return 0

    if args.command == "list":
        print("strategies:")
        for name in sorted(set(STRATEGY_SCRIPTS)):
            print(f"  {name} -> {STRATEGY_SCRIPTS[name]}")
        root = Path(cfg.trace_root)
        runs = sorted(p.name for p in root.iterdir() if p.is_dir()) \
            if root.exists() else []
        print(f"runs under {root}:" if runs else f"no runs under {root}")
        for r in runs:
            print(f"  {r}")
        return 0
    return 2


if __name__ == "__main__":
    sys.exit(main())
