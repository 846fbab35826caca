"""Standalone per-job server — the reference's job pod, as a process.

Parity with the reference's pod-per-job deployment: the PS creates one
pod per training job running `/kubeml --jobPort 9090 --jobId <id>`
(ml/pkg/ps/job_pod.go:140-217) whose TrainJob exposes a per-job REST API
(ml/pkg/train/api.go:141-149). Here the job is a child PROCESS on the TPU
host with the same surface:

    POST   /start     receive the TrainTask, begin training
    POST   /update    next-epoch parallelism push {"parallelism": N}
    DELETE /stop      graceful stop at the next epoch boundary
    GET    /health    readiness probe (built into JsonService)

(The reference's POST /next/{funcId} merge barrier has no equivalent:
the N serverless functions collapsed into the compiled K-avg round, so
there is no per-function HTTP rendezvous — SURVEY.md §2b.)

Control-plane callbacks run over HTTP, exactly like the reference job
pod: metric pushes to the PS (`POST {ps}/metrics/{jobId}`,
ml/pkg/train/util.go:19-50), re-parallelization requests to the scheduler
(`POST {scheduler}/job` then block for the PS-relayed `/update`,
ml/pkg/train/job.go:196-215), and the finish notification
(`POST {ps}/finish/{jobId}`, ml/pkg/ps/client/client.go:142-160).

Run directly (the reference's `--jobPort --jobId` role of the single
binary, ml/cmd/ml/main.go:60-156):

    python -m kubeml_tpu.train.jobserver --job-id abc123 \
        --ps-url http://host:port --scheduler-url http://host:port \
        [--port 9090] [--port-file /path] [--mesh-data N] \
        [--virtual-cpu-devices N]
"""

from __future__ import annotations

import argparse
import logging
import os
import random
import threading
import time
import zlib
from typing import Optional

from kubeml_tpu.api.errors import (InvalidArgsError, JobPreemptedError,
                                   KubeMLException)
from kubeml_tpu.api.types import MetricUpdate, TrainTask
from kubeml_tpu.control.httpd import JsonService, Request, http_json

logger = logging.getLogger("kubeml_tpu.jobserver")


class JobServer(JsonService):
    name = "job"

    def __init__(self, job_id: str, ps_url: Optional[str] = None,
                 scheduler_url: Optional[str] = None, port: int = 0,
                 mesh=None, trace_id: Optional[str] = None):
        super().__init__(port=port)
        self.job_id = job_id
        self.ps_url = ps_url
        self.scheduler_url = scheduler_url
        self.mesh = mesh
        # propagated over argv by the PS spawn (falls back to the task's
        # wire field in _launch) so this process's spans join the
        # client-minted trace
        self.trace_id = trace_id
        self.finished = threading.Event()  # set after the job ends
        self.exit_error: Optional[str] = None
        self._job = None
        self._job_thread: Optional[threading.Thread] = None
        self._hb_thread: Optional[threading.Thread] = None
        # progress heartbeats to the PS liveness reaper; 0 disables
        self.heartbeat_interval = float(
            os.environ.get("KUBEML_HEARTBEAT_INTERVAL", "10"))
        self._next_parallelism: Optional[int] = None
        self._update_event = threading.Event()
        # backoff jitter source for control-plane callbacks, seeded from
        # the job id so a test run replays the same retry schedule
        self._rng = random.Random(zlib.crc32(job_id.encode()))

        self.route("POST", "/start", self._h_start)
        self.route("POST", "/update", self._h_update)
        self.route("DELETE", "/stop", self._h_stop)

    # ------------------------------------------------------------- handlers

    def _h_start(self, req: Request):
        if self._job is not None:
            raise InvalidArgsError(f"job {self.job_id} already started")
        task = TrainTask.from_dict(req.body)
        if task.job_id != self.job_id:
            raise InvalidArgsError(
                f"task {task.job_id} sent to job server {self.job_id}")
        self._launch(task)
        return {"job_id": self.job_id}

    def _h_update(self, req: Request):
        self._next_parallelism = int(req.body["parallelism"])
        epoch = req.body.get("grant_epoch")
        if epoch is not None and self._job is not None:
            # durable control plane: a recovered scheduler re-grants
            # surviving jobs under a new fencing epoch and relays it
            # here — adopt it so the next /job ask presents the current
            # epoch instead of being 409'd as a stale pre-crash grant
            self._job.task.grant_epoch = int(epoch)
        self._update_event.set()
        return {"ok": True}

    def _h_stop(self, req: Request):
        if self._job is None:
            raise InvalidArgsError("job not started")
        self._job.stop()
        return {"ok": True}

    # ------------------------------------------------------------ lifecycle

    def _launch(self, task: TrainTask):
        from kubeml_tpu.api.const import kubeml_home
        from kubeml_tpu.data.registry import DatasetRegistry
        from kubeml_tpu.models.base import KubeDataset
        from kubeml_tpu.parallel.mesh import make_mesh
        from kubeml_tpu.train.functionlib import FunctionRegistry
        from kubeml_tpu.train.history import HistoryStore
        from kubeml_tpu.train.job import JobCallbacks, TrainJob

        task.trace_id = task.trace_id or self.trace_id or ""
        fn_name = task.parameters.function_name or task.parameters.model_type
        model_cls, dataset_cls = FunctionRegistry().resolve(fn_name)
        model = model_cls()
        dataset = (dataset_cls(task.parameters.dataset) if dataset_cls
                   else KubeDataset(task.parameters.dataset))
        self._job = TrainJob(
            task, model, dataset,
            self.mesh if self.mesh is not None else make_mesh(),
            registry=DatasetRegistry(),
            history_store=HistoryStore(),
            callbacks=JobCallbacks(
                request_parallelism=self._request_parallelism,
                publish_metrics=self._publish_metrics,
                on_finish=self._on_finish),
            log_file=os.path.join(kubeml_home(), "logs",
                                  f"{task.job_id}.log"))
        self._job_thread = threading.Thread(
            target=self._run, name=f"job-{self.job_id}", daemon=True)
        self._job_thread.start()
        if self.ps_url is not None and self.heartbeat_interval > 0:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop,
                name=f"heartbeat-{self.job_id}", daemon=True)
            self._hb_thread.start()

    def _post_with_retry(self, what: str, url: str, body: dict,
                         attempts: int = 5, base_delay: float = 0.05,
                         max_delay: float = 2.0) -> bool:
        """Control-plane callback with bounded, jittered exponential
        backoff: a PS or scheduler that is mid-restart (durable control
        plane) is back within a moment, so a short retry window turns a
        lost notification into a late one. Bounded — after `attempts`
        the loss is logged and the control plane's own backstops (the
        PS liveness reaper, the scheduler recovery sweep) take over.
        Jitter comes from the job-id-seeded RNG so runs replay the same
        schedule."""
        delay = base_delay
        for attempt in range(attempts):
            try:
                http_json("POST", url, body)
                return True
            except KubeMLException as e:
                if attempt == attempts - 1:
                    logger.warning("%s failed after %d attempt(s): %s",
                                   what, attempts, e.message)
                    return False
                logger.debug("%s attempt %d failed (%s); retrying",
                             what, attempt + 1, e.message)
                time.sleep(delay * (0.5 + self._rng.random() / 2))
                delay = min(delay * 2, max_delay)
        return False

    def _run(self):
        try:
            self._job.train()
        except JobPreemptedError as e:
            # graceful preemption: the round-granular checkpoint is on
            # disk; tell the PS so its watchdog reschedules this job
            # (deliberately NOT /finish — that would tear down the job
            # record the restart needs)
            logger.warning("job %s preempted at epoch %d round %d; "
                           "notifying PS", self.job_id, e.epoch, e.round)
            if self.ps_url is not None:
                self._post_with_retry(
                    "preemption notification",
                    f"{self.ps_url}/preempted/{self.job_id}",
                    {"epoch": e.epoch, "round": e.round})
            self.finished.set()
        except Exception:
            logger.exception("job %s failed", self.job_id)
            self.finished.set()  # train() reports on_finish itself; backstop

    def preempt(self):
        """SIGTERM entry: ask the job to drain the in-flight round,
        checkpoint at the round cursor, and exit for rescheduling."""
        job = self._job
        if job is not None:
            logger.warning("job server %s: preemption notice (SIGTERM); "
                           "draining in-flight round", self.job_id)
            job.preempt()
        else:
            # no task yet — nothing to drain, just exit cleanly
            self.finished.set()

    def _heartbeat_loop(self):
        """Progress heartbeats (epoch, round cursor) to the PS liveness
        reaper — a job that stops posting for the miss budget is
        declared wedged and restarted from its round checkpoint. Paced
        on the finished event, never time.sleep, so shutdown is prompt."""
        while not self.finished.wait(timeout=self.heartbeat_interval):
            job = self._job
            if job is None:
                continue
            epoch, rnd = getattr(job, "_progress", (0, 0))
            # short bounded retry (not the full budget): a beat lost to
            # a PS restart costs a reaper miss, but the NEXT beat is
            # only heartbeat_interval away, so don't stall this loop
            self._post_with_retry(
                "heartbeat", f"{self.ps_url}/heartbeat/{self.job_id}",
                {"epoch": int(epoch), "round": int(rnd)},
                attempts=3, max_delay=0.5)

    # ------------------------------------------------------------ callbacks

    def _request_parallelism(self, task: TrainTask) -> Optional[int]:
        """job.go:196-215 over HTTP: ask the scheduler, then block for the
        PS-relayed POST /update."""
        if self.scheduler_url is None:
            return None
        self._update_event.clear()
        try:
            http_json("POST", f"{self.scheduler_url}/job", task.to_dict())
        except KubeMLException as e:
            logger.warning("scheduler unreachable: %s", e.message)
            return None
        if not self._update_event.wait(timeout=60.0):
            logger.warning("no parallelism update within 60s")
            return None
        self._update_event.clear()
        return self._next_parallelism

    def _publish_metrics(self, m: MetricUpdate):
        if self.ps_url is None:
            return
        try:
            http_json("POST", f"{self.ps_url}/metrics/{self.job_id}",
                      m.to_dict())
        except KubeMLException as e:
            logger.warning("metric push failed: %s", e.message)

    def _on_finish(self, job_id: str, error: Optional[str]):
        self.exit_error = error
        if self.ps_url is not None:
            self._post_with_retry("finish notification",
                                  f"{self.ps_url}/finish/{job_id}",
                                  {"error": error})
        self.finished.set()


def main(argv=None):
    p = argparse.ArgumentParser(prog="kubeml-job")
    p.add_argument("--job-id", required=True)
    p.add_argument("--ps-url", default=None)
    p.add_argument("--scheduler-url", default=None)
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--port-file", default=None,
                   help="write the bound port here (parent discovery)")
    p.add_argument("--mesh-data", type=int, default=0,
                   help="data-axis size (default: all devices)")
    p.add_argument("--virtual-cpu-devices", type=int, default=0,
                   help="retarget JAX at N virtual CPU devices (tests)")
    p.add_argument("--trace-id", default=os.environ.get("KUBEML_TRACE_ID"),
                   help="trace id minted by the client (cross-process "
                        "span correlation)")
    args = p.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    # process entry: same persistent compile cache as the parent PS
    # (utils/env.py) — a restarted job deserializes its round program
    from kubeml_tpu.utils.env import enable_compile_cache
    enable_compile_cache()
    # a wedged child (backend init, collective, IO) is otherwise a
    # silent readiness-timeout for the PS: dump every thread's stack to
    # stderr periodically so the parent's captured output shows WHERE
    # (same discipline as the distributed test workers). The period is
    # tied to the start window so a healthy-but-slow start (heavy host
    # load can push JAX init to minutes) produces at most ~one dump
    # before either the task arrives or the PS gives up — not a
    # traceback flood every two minutes
    import faulthandler
    start_window = float(os.environ.get("KUBEML_JOB_START_TIMEOUT",
                                        120.0)) + 180.0
    faulthandler.dump_traceback_later(max(60.0, start_window / 2),
                                      repeat=True)
    if args.virtual_cpu_devices:
        from kubeml_tpu.parallel.distributed import _cluster_env_present
        if _cluster_env_present():
            # the no-silent-degrade guarantee (parallel/distributed.py):
            # a declared cluster must never fall back to N independent
            # single-process trainings
            raise RuntimeError(
                "--virtual-cpu-devices is single-process by "
                "construction but the environment declares a "
                "jax.distributed cluster; unset the cluster variables "
                "or drop the flag")
        from kubeml_tpu.testing import ensure_virtual_cpu_devices
        ensure_virtual_cpu_devices(args.virtual_cpu_devices)
    else:
        # multi-host job pods join the jax.distributed cluster before
        # any JAX call (auto-discovery / KUBEML_* env; single-host
        # no-ops)
        from kubeml_tpu.parallel.distributed import initialize
        initialize()

    from kubeml_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(n_data=args.mesh_data or None)
    server = JobServer(args.job_id, ps_url=args.ps_url,
                       scheduler_url=args.scheduler_url, port=args.port,
                       mesh=mesh, trace_id=args.trace_id)
    port = server.start()
    # preemption grace: SIGTERM (the platform's eviction notice) drains
    # the in-flight round, publishes a round-granular checkpoint and
    # posts /preempted to the PS instead of dying mid-round. The handler
    # only sets events — all real work happens on the training thread.
    import signal
    signal.signal(signal.SIGTERM, lambda *_: server.preempt())
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(port))
        os.replace(tmp, args.port_file)  # atomic: parent never reads partial
    logger.info("job server %s on port %d", args.job_id, port)
    # bounded wait for the task: a child whose parent died (or whose
    # /start push was lost) must not linger as an idle orphan forever —
    # observed exactly that when a PS teardown raced a crash-restart's
    # /start push. Once training starts, the wait is unbounded (the job
    # itself decides when it is finished).
    start_timeout = start_window  # parsed once, above
    while not server.finished.wait(timeout=30.0):
        if server._job is not None:
            if start_timeout is not None:
                start_timeout = None  # task arrived: wait indefinitely
                # the watchdog dumps exist to diagnose a wedged START;
                # a healthy long-running job must not flood stderr with
                # all-thread tracebacks every two minutes
                faulthandler.cancel_dump_traceback_later()
        elif start_timeout is not None:
            start_timeout -= 30.0
            if start_timeout <= 0:
                logger.error("job server %s received no task within the "
                             "start window; exiting", args.job_id)
                break
    server.stop()


if __name__ == "__main__":
    main()
