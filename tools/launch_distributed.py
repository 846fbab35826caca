"""Multi-process distributed launcher.

Spawns N OS processes of one command, each joined into a single
`jax.distributed` cluster via the KUBEML_* environment contract that
`kubeml_tpu.parallel.distributed.initialize()` (and therefore `kubeml
serve` / the jobserver) reads at startup:

    KUBEML_COORDINATOR_ADDRESS   host:port of process 0
    KUBEML_NUM_PROCESSES         total process count
    KUBEML_PROCESS_ID            this process's rank

Two modes:

  --emulate-cpu D     CPU emulation on ONE machine: each process gets D
                      virtual CPU devices (JAX_PLATFORMS=cpu,
                      JAX_NUM_CPU_DEVICES=D) — the supported way to
                      exercise the multi-process code path without N TPU hosts. The
                      2-process CI test drives exactly this mode.
  (default)           one process per invocation of this tool per HOST
                      (real multi-host): run the SAME command on every
                      host with --process-id set per host; devices are
                      the host's real chips. On Cloud TPU pod slices
                      prefer no launcher at all — `initialize()`
                      auto-discovers from the TPU metadata environment.

Replaces the role the reference's in-process harness plays
(/root/reference/ml/tests/integration.go:14-36): bring up a multi-process
deployment without a real cluster.

Examples:

    # 2 processes x 4 virtual CPU devices running a worker script
    python -m tools.launch_distributed --processes 2 --emulate-cpu 4 \
        -- python my_worker.py

    # real 2-host bring-up (run once per host)
    python -m tools.launch_distributed --processes 2 --process-id 0 \
        --coordinator host0:12355 -- python -m kubeml_tpu.cli.main serve
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _stream(proc: subprocess.Popen, rank: int) -> None:
    for line in proc.stdout:
        sys.stdout.write(f"[p{rank}] {line.decode(errors='replace')}")
        sys.stdout.flush()


def _checkpoint_durable(root: str, job_id: str) -> bool:
    """JAX-free mirror of train/checkpoint._resolve_dir + saved_at: does
    `root` hold a complete checkpoint for `job_id` (current or the
    mid-publish .old fallback)? The supervisor must not import jax — on
    a TPU host the chips belong to the worker processes."""
    base = os.path.join(root, job_id)
    for d in (base, base + ".old"):
        try:
            with open(os.path.join(d, "manifest.json")) as f:
                if json.load(f).get("saved_at") is not None:
                    return True
        except (OSError, ValueError):
            continue
    return False


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="launch_distributed",
        description="spawn a jax.distributed multi-process run")
    p.add_argument("--processes", type=int, required=True, metavar="N")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="coordinator address (default: localhost + a "
                        "free port — emulation mode only)")
    p.add_argument("--process-id", type=int, default=None,
                   help="rank of THIS host's process (real multi-host "
                        "mode: spawn exactly one process)")
    p.add_argument("--emulate-cpu", type=int, default=0, metavar="D",
                   help="spawn ALL N processes locally, each with D "
                        "virtual CPU devices")
    p.add_argument("--fail-fast", action="store_true",
                   help="when any rank exits nonzero, kill the remaining "
                        "ranks instead of waiting (a dead rank leaves "
                        "survivors blocked in a collective indefinitely; "
                        "the supervisor, not a collective timeout, should "
                        "tear the cluster down so recovery can restart it)")
    p.add_argument("--max-restarts", type=int, default=0, metavar="R",
                   help="SUPERVISOR mode (with --fail-fast): after a "
                        "nonzero teardown, relaunch the whole cluster up "
                        "to R times with KUBEML_RESTART_COUNT incremented "
                        "— the worker contract for resuming its job from "
                        "its own checkpoint (resume_from = job id), the "
                        "distributed counterpart of the PS watchdog's "
                        "checkpoint restart (control/ps.py). Eligibility "
                        "mirrors the watchdog: budget not exhausted, not "
                        "interrupted, and (when --restart-job is given) a "
                        "durable checkpoint on every --checkpoint-root")
    p.add_argument("--restart-job", default=None, metavar="JOB_ID",
                   help="job id whose durable checkpoint gates a restart")
    p.add_argument("--checkpoint-root", action="append", default=[],
                   metavar="DIR",
                   help="models dir(s) probed for --restart-job's "
                        "checkpoint (repeatable: one per rank home); a "
                        "restart needs ALL of them — SPMD ranks "
                        "checkpoint in lockstep, so a missing one means "
                        "the crash predates durable state")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="command to run (prefix with --)")
    args = p.parse_args(argv)
    if args.max_restarts and not args.fail_fast:
        p.error("--max-restarts requires --fail-fast (without teardown "
                "a wounded cluster never returns control to restart)")

    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        p.error("no command given (append: -- python your_script.py ...)")

    auto_coordinator = args.coordinator is None
    if auto_coordinator and args.emulate_cpu <= 0:
        p.error("--coordinator is required outside --emulate-cpu mode")

    base_env = dict(os.environ,
                    KUBEML_NUM_PROCESSES=str(args.processes))

    if args.emulate_cpu > 0:
        ranks = list(range(args.processes))
        # the one shared recipe for CPU-targeting a child from
        # interpreter start (JAX-free import)
        from kubeml_tpu.testing import virtual_cpu_env
        base_env.update(virtual_cpu_env(args.emulate_cpu))
    else:
        if args.process_id is None:
            p.error("--process-id is required in real multi-host mode")
        ranks = [args.process_id]

    import time as _time
    interrupted = False

    def run_once(attempt: int) -> int:
        """One cluster incarnation: spawn every rank, wait (or poll with
        fail-fast teardown), return the first casualty's exit code."""
        nonlocal interrupted
        # a fresh coordinator port per incarnation: the dead
        # coordinator's socket can linger in TIME_WAIT and fail the
        # restart's bind (auto-assigned / emulation mode only — an
        # explicit --coordinator is the operator's to manage)
        coordinator = (f"localhost:{_free_port()}" if auto_coordinator
                       else args.coordinator)
        env0 = dict(base_env, KUBEML_COORDINATOR_ADDRESS=coordinator,
                    KUBEML_RESTART_COUNT=str(attempt))
        procs, threads = [], []
        for rank in ranks:
            env = dict(env0, KUBEML_PROCESS_ID=str(rank))
            proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                    stderr=subprocess.STDOUT)
            t = threading.Thread(target=_stream, args=(proc, rank),
                                 daemon=True)
            t.start()
            procs.append(proc)
            threads.append(t)

        rc = 0
        try:
            if args.fail_fast:
                live = list(procs)
                while live:
                    for proc in list(live):
                        code = proc.poll()
                        if code is None:
                            continue
                        live.remove(proc)
                        if code and not rc:
                            # report the FIRST casualty's code, not the
                            # -9s of the survivors this teardown is
                            # about to kill
                            rc = code
                            for other in live:
                                other.kill()
                    _time.sleep(0.1)
            else:
                for proc in procs:
                    rc = proc.wait() or rc
        except KeyboardInterrupt:
            # the watchdog's "acknowledged stop" rule: an operator
            # interrupt must never be undone by a supervisor restart
            interrupted = True
            for proc in procs:
                proc.send_signal(signal.SIGINT)
            for proc in procs:
                rc = proc.wait() or rc
        for t in threads:
            t.join(timeout=5)
        return rc

    attempt = 0
    while True:
        rc = run_once(attempt)
        if rc == 0 or interrupted or attempt >= args.max_restarts:
            return rc
        if args.restart_job and args.checkpoint_root and not all(
                _checkpoint_durable(root, args.restart_job)
                for root in args.checkpoint_root):
            sys.stderr.write(
                f"supervisor: rank failed (rc={rc}) but job "
                f"{args.restart_job} has no durable checkpoint on every "
                "rank — nothing to resume, giving up\n")
            return rc
        attempt += 1
        sys.stderr.write(
            f"supervisor: cluster died (rc={rc}); relaunching with "
            f"KUBEML_RESTART_COUNT={attempt} "
            f"(restart {attempt}/{args.max_restarts})\n")


if __name__ == "__main__":
    raise SystemExit(main())
