"""Standalone (process-per-job) mode: PS spawns a jobserver child process
and speaks the reference's per-job REST surface to it.

Mirrors the reference's STANDALONE_JOBS=true pod-per-job deployment
(ml/pkg/ps/job_pod.go + ml/pkg/train/api.go:141-149): job in its own
process, /start pushed with retries after readiness, scheduler updates
relayed through PS POST /update/{jobId} -> job POST /update, metric and
finish notifications flowing back over HTTP.
"""

import time

import numpy as np
import pytest

from kubeml_tpu.api.errors import KubeMLException
from kubeml_tpu.api.types import TrainOptions, TrainRequest
from kubeml_tpu.control.client import KubemlClient
from kubeml_tpu.control.deployment import start_deployment

from tests.test_control_plane import wait_history, write_blob_files


@pytest.fixture()
def standalone_stack(tmp_path, tmp_home, mesh8, monkeypatch):
    monkeypatch.setenv("STANDALONE_JOBS", "true")
    # CI runs many JAX processes concurrently; a child's import/init can
    # exceed the 120 s production default, which would fail the start
    # (or eat a chaos test's restart budget) spuriously
    monkeypatch.setenv("KUBEML_JOB_START_TIMEOUT", "600")
    dep = start_deployment(mesh=mesh8)
    assert dep.ps.standalone_jobs
    client = KubemlClient(dep.controller_url)
    yield dep, client, tmp_path
    dep.stop()


def test_standalone_train_updates_and_infer(standalone_stack):
    dep, client, tmp_path = standalone_stack
    paths = write_blob_files(tmp_path)
    client.v1().datasets().create(
        "blobs", paths["xtr"], paths["ytr"], paths["xte"], paths["yte"])

    # dynamic parallelism: exercises the full relay chain
    # child -> scheduler /job -> PS /update/{jobId} -> child /update
    req = TrainRequest(model_type="mlp", batch_size=32, epochs=3,
                       dataset="blobs", lr=0.1,
                       options=TrainOptions(default_parallelism=2, k=2))
    trace_id = "feed0123beef4567"
    job_id = client.v1().networks().train(req, trace_id=trace_id)

    # the job must be running as a child process, not a thread (records
    # are reserved before the spawn, so wait for the url to be set)
    deadline = time.time() + 180
    rec = None
    while time.time() < deadline:
        with dep.ps._jobs_lock:
            rec = dep.ps.jobs.get(job_id)
        if rec is not None and rec.url is not None:
            break
        time.sleep(0.2)
    assert rec is not None, "job record never appeared"
    assert rec.proc is not None and rec.url is not None
    assert rec.thread is None and rec.job is None

    history = wait_history(client, job_id, timeout=240)
    assert len(history.data.train_loss) == 3
    # throughput policy always scales up on the second decision
    assert history.data.parallelism[0] == 2
    assert history.data.parallelism[1] >= 2

    # child process reaped after finish; metrics series cleared
    assert dep.ps.wait_for_job(job_id, timeout=30)
    assert f'jobid="{job_id}"' not in dep.ps.metrics.exposition()

    # cross-process trace correlation: the client-minted trace id
    # appears in spans recorded by the standalone CHILD process (its
    # trace file is pid-suffixed with the child's pid, not ours)
    import os
    from kubeml_tpu.utils.trace import merge_job_trace
    doc = merge_job_trace(job_id)
    assert doc["metadata"]["trace_ids"] == [trace_id]
    child_pids = {int(s.split("-")[1].split(".")[0])
                  for s in doc["metadata"]["sources"]
                  if s.startswith("job-")}
    assert child_pids and os.getpid() not in child_pids
    epochs = [e for e in doc["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "epoch"]
    assert len(epochs) == 3
    assert all(e["args"]["trace_id"] == trace_id
               and e["pid"] in child_pids for e in epochs)

    # inference from the checkpoint written by the CHILD process
    x = np.load(paths["xte"])[:5]
    preds = client.v1().networks().infer(job_id, x.tolist())
    assert len(preds) == 5


def test_standalone_stop(standalone_stack):
    dep, client, tmp_path = standalone_stack
    paths = write_blob_files(tmp_path)
    client.v1().datasets().create(
        "blobs", paths["xtr"], paths["ytr"], paths["xte"], paths["yte"])

    req = TrainRequest(model_type="mlp", batch_size=16, epochs=500,
                       dataset="blobs", lr=0.05,
                       options=TrainOptions(default_parallelism=2,
                                            static_parallelism=True, k=1))
    job_id = client.v1().networks().train(req)

    # wait until it is actually training, then stop through the controller
    deadline = time.time() + 180
    while time.time() < deadline:
        tasks = client.v1().tasks().list()
        if any(t.job_id == job_id and t.state == "running" for t in tasks):
            break
        time.sleep(0.3)
    client.v1().tasks().stop(job_id)

    assert dep.ps.wait_for_job(job_id, timeout=240), "job did not stop"
    # a stopped job still records its partial history (job.go:250-260)
    history = wait_history(client, job_id, timeout=60)
    assert len(history.data.train_loss) < 500


@pytest.fixture()
def partitioned_stack(tmp_path, tmp_home, monkeypatch):
    """Standalone PS with TWO device-partition slots, each exposing its
    own 2-virtual-CPU-device view to the job process (the single-chip
    stand-in for per-job TPU_VISIBLE_DEVICES pinning)."""
    from kubeml_tpu.testing import virtual_cpu_env
    dep = start_deployment(mesh=None, standalone_jobs=True,
                           job_partitions=[virtual_cpu_env(2),
                                           virtual_cpu_env(2)])
    client = KubemlClient(dep.controller_url)
    yield dep, client, tmp_path
    dep.stop()


def test_dual_standalone_jobs_with_partitions(partitioned_stack):
    """Two CONCURRENT standalone jobs, each leasing its own device
    partition (distinct slots while running); a third submission while
    both slots are leased is refused 503; slots free after the
    processes exit and a new job starts (VERDICT r1 item 10)."""
    from kubeml_tpu.api.types import TrainTask
    from kubeml_tpu.control.httpd import http_json

    dep, client, tmp_path = partitioned_stack
    paths = write_blob_files(tmp_path, n_train=2000)
    client.v1().datasets().create(
        "blobs", paths["xtr"], paths["ytr"], paths["xte"], paths["yte"])

    req = TrainRequest(model_type="mlp", batch_size=16, epochs=4,
                       dataset="blobs", lr=0.05,
                       options=TrainOptions(default_parallelism=2, k=1,
                                            static_parallelism=True))
    ids = [client.v1().networks().train(req) for _ in range(2)]

    # both running as processes, each holding a DIFFERENT partition
    deadline = time.time() + 240
    held = {}
    while time.time() < deadline and len(held) < 2:
        with dep.ps._jobs_lock:
            for jid in ids:
                rec = dep.ps.jobs.get(jid)
                if rec is not None and rec.partition is not None:
                    held[jid] = rec.partition
        time.sleep(0.1)
    assert sorted(held.values()) == [0, 1], held

    # a direct /start while both slots are leased: PS refuses 503
    extra = TrainTask(job_id="overflow1", parameters=req, parallelism=2)
    with pytest.raises(KubeMLException) as ei:
        http_json("POST", dep.ps.url + "/start", extra.to_dict())
    assert ei.value.status_code == 503

    # ... while the PRODUCT path does not lose the job: the scheduler
    # requeues on 503 and starts it once a slot frees
    third = client.v1().networks().train(req)

    for jid in ids:
        h = wait_history(client, jid, timeout=300)
        assert len(h.data.train_loss) == 4
        assert h.data.train_loss[-1] < h.data.train_loss[0]
    h = wait_history(client, third, timeout=300)
    assert len(h.data.train_loss) == 4
    for jid in ids + [third]:
        dep.ps.wait_for_job(jid)

    # every slot released once the processes are gone
    deadline = time.time() + 60
    while time.time() < deadline and dep.ps._busy_partitions:
        time.sleep(0.1)
    assert not dep.ps._busy_partitions


def test_crashed_job_process_releases_partition(partitioned_stack):
    """A child that dies WITHOUT posting /finish (OOM-kill, segfault)
    must not pin its record or its device partition: the PS watchdog
    reaps it and frees the slot."""
    dep, client, tmp_path = partitioned_stack
    paths = write_blob_files(tmp_path, n_train=4000)
    client.v1().datasets().create(
        "blobs", paths["xtr"], paths["ytr"], paths["xte"], paths["yte"])
    req = TrainRequest(model_type="mlp", batch_size=16, epochs=50,
                       dataset="blobs", lr=0.05,
                       options=TrainOptions(default_parallelism=2, k=1,
                                            static_parallelism=True,
                                            max_restarts=0))
    job_id = client.v1().networks().train(req)
    deadline = time.time() + 240
    rec = None
    while time.time() < deadline:
        with dep.ps._jobs_lock:
            rec = dep.ps.jobs.get(job_id)
        if rec is not None and rec.url is not None:
            break
        time.sleep(0.1)
    assert rec is not None and rec.partition is not None
    rec.proc.kill()  # simulated OOM-kill; max_restarts=0 => must NOT respawn
    deadline = time.time() + 60
    while time.time() < deadline:
        with dep.ps._jobs_lock:
            gone = job_id not in dep.ps.jobs
        if gone and not dep.ps._busy_partitions:
            break
        time.sleep(0.1)
    assert gone
    assert not dep.ps._busy_partitions


# ------------------------------------------- crash-injection machinery
#
# Shared by the recovery chaos tests below. Kill windows are kept tens
# of seconds wide through n_train sizing (~1 s/epoch x tens of epochs):
# at 0.2 s/epoch the job could finish before a load-starved poll thread
# landed the kill (measured flaky under a concurrent full-tier run).


def _read_manifest(tmp_home, job_id) -> dict:
    import json
    import os
    try:
        with open(os.path.join(str(tmp_home), "models", job_id,
                               "manifest.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _kill_in_window(dep, tmp_home, job_id, epochs, expect_restarts=0,
                    timeout=240.0, min_epoch=1, sig=None):
    """Wait for the job's incarnation `expect_restarts` to be fully
    RUNNING (task state 'running' — a kill between readiness and the
    /start push would hit a child that never received its task) with a
    durable MID-JOB checkpoint (min_epoch <= manifest epoch < epochs),
    then SIGKILL it (or send `sig`, e.g. SIGTERM for the preemption
    grace path). min_epoch > 1 lets chained-crash tests require the
    CURRENT incarnation to have checkpointed (not just the previous
    one's leftover manifest). Returns the record."""
    deadline = time.time() + timeout
    seen = False
    while time.time() < deadline:
        with dep.ps._jobs_lock:
            rec = dep.ps.jobs.get(job_id)
        if rec is None:
            # BEFORE the job ever registered this is just the scheduler's
            # asynchronous dispatch not having run yet (the queue loop
            # picks the task moments after submit — a fast poll can beat
            # it); AFTER it registered, a vanished record means the job
            # ended and the test's premise is broken
            assert not seen, "job ended before the kill window"
            time.sleep(0.05)
            continue
        seen = True
        if rec.restarts == expect_restarts and rec.proc is not None \
                and rec.url is not None \
                and rec.task.state == "running" and \
                min_epoch <= _read_manifest(tmp_home, job_id
                                            ).get("epoch", 0) < epochs:
            if sig is None:
                rec.proc.kill()
            else:
                rec.proc.send_signal(sig)
            return rec
        time.sleep(0.05)
    raise AssertionError("kill window never opened")


def test_crashed_job_restarts_from_checkpoint(standalone_stack, tmp_home):
    """Checkpoint-based crash recovery (VERDICT r3 item 2): SIGKILL the
    standalone job process mid-job, after at least one periodic
    checkpoint is durable. The PS watchdog must respawn it with
    resume_from = its own job id; the restarted process restores the
    completed epochs' history from the checkpoint manifest and runs the
    job to completion — one continuous history, state 'finished', and
    the pre-crash epoch metrics preserved verbatim. The reference loses
    the job when its TrainJob pod dies (tolerance exists only within a
    merge, util.go:144-166)."""
    dep, client, tmp_path = standalone_stack
    paths = write_blob_files(tmp_path, n_train=4000)
    client.v1().datasets().create(
        "blobs", paths["xtr"], paths["ytr"], paths["xte"], paths["yte"])

    epochs = 30
    req = TrainRequest(model_type="mlp", batch_size=16, epochs=epochs,
                       dataset="blobs", lr=0.05,
                       options=TrainOptions(default_parallelism=2, k=1,
                                            static_parallelism=True,
                                            max_restarts=1,
                                            # no goal-accuracy early
                                            # stop: a fast-converging
                                            # run must not end before
                                            # the kill lands
                                            goal_accuracy=200.0))
    job_id = client.v1().networks().train(req)

    rec = _kill_in_window(dep, tmp_home, job_id, epochs)  # the crash
    pre_crash = _read_manifest(tmp_home, job_id)
    assert pre_crash.get("history"), "mid-job manifest must carry history"

    # the SAME record must be respawned (not failed): restarts consumed,
    # new child process, job still registered
    deadline = time.time() + 120
    while time.time() < deadline:
        with dep.ps._jobs_lock:
            alive = dep.ps.jobs.get(job_id)
        if alive is not None and alive.restarts == 1:
            break
        if alive is None:
            break  # may have already finished post-restart — checked below
        time.sleep(0.1)

    history = wait_history(client, job_id, timeout=300)
    assert rec.restarts == 1, "watchdog did not restart the crashed job"
    # one CONTINUOUS history across the crash: full epoch count, and the
    # pre-crash epochs' metrics preserved verbatim from the manifest
    assert len(history.data.train_loss) == epochs
    saved = pre_crash["history"]["train_loss"]
    assert history.data.train_loss[: len(saved)] == saved
    assert dep.ps.wait_for_job(job_id, timeout=60)

    # the finished model is inferable like any other
    x = np.load(paths["xte"])[:3]
    preds = client.v1().networks().infer(job_id, x.tolist())
    assert len(preds) == 3


def test_two_crashes_two_restarts_continuous_history(standalone_stack,
                                                     tmp_home):
    """max_restarts=2 survives TWO crashes: the second restart resumes
    from the checkpoint the FIRST restarted incarnation wrote (chained
    resume-from-self), and the final history is one continuous run."""
    dep, client, tmp_path = standalone_stack
    paths = write_blob_files(tmp_path, n_train=20000)
    client.v1().datasets().create(
        "blobs", paths["xtr"], paths["ytr"], paths["xte"], paths["yte"])
    epochs = 40
    req = TrainRequest(model_type="mlp", batch_size=16, epochs=epochs,
                       dataset="blobs", lr=0.05,
                       options=TrainOptions(default_parallelism=2, k=1,
                                            static_parallelism=True,
                                            max_restarts=2,
                                            goal_accuracy=200.0))
    job_id = client.v1().networks().train(req)

    _kill_in_window(dep, tmp_home, job_id, epochs, expect_restarts=0)
    first_crash_epoch = _read_manifest(tmp_home, job_id).get("epoch", 0)
    assert first_crash_epoch >= 1
    # require the RESTARTED incarnation to have checkpointed past the
    # first crash's manifest before the second kill, so the third
    # incarnation genuinely resumes from incarnation #2's checkpoint
    # (chained recovery), not a single-hop resume of the first one
    rec = _kill_in_window(dep, tmp_home, job_id, epochs,
                          expect_restarts=1,
                          min_epoch=first_crash_epoch + 1)
    second_crash = _read_manifest(tmp_home, job_id)
    assert second_crash.get("epoch", 0) > first_crash_epoch

    history = wait_history(client, job_id, timeout=420)
    assert rec.restarts == 2
    assert len(history.data.train_loss) == epochs
    # the third incarnation's restored prefix equals what was durable
    # at the second crash — history chained across BOTH restarts
    saved = second_crash["history"]["train_loss"]
    assert history.data.train_loss[: len(saved)] == saved
    assert dep.ps.wait_for_job(job_id, timeout=120)


def test_restart_budget_exhausted_fails_job(standalone_stack, tmp_home):
    """A second crash beyond max_restarts=1 must FAIL the job (no
    infinite respawn loop): the watchdog consumes its one restart on
    the first kill, and the second kill deregisters the job with the
    unexpected-exit error."""
    dep, client, tmp_path = standalone_stack
    paths = write_blob_files(tmp_path, n_train=20000)
    client.v1().datasets().create(
        "blobs", paths["xtr"], paths["ytr"], paths["xte"], paths["yte"])
    epochs = 40
    req = TrainRequest(model_type="mlp", batch_size=16, epochs=epochs,
                       dataset="blobs", lr=0.05,
                       options=TrainOptions(default_parallelism=2, k=1,
                                            static_parallelism=True,
                                            max_restarts=1,
                                            # no goal-accuracy early
                                            # stop: a fast-converging
                                            # run must not end before
                                            # the kill lands
                                            goal_accuracy=200.0))
    job_id = client.v1().networks().train(req)

    # first kill: consumed by the one restart; second: budget exhausted
    _kill_in_window(dep, tmp_home, job_id, epochs, expect_restarts=0)
    rec = _kill_in_window(dep, tmp_home, job_id, epochs,
                          expect_restarts=1)

    # the job must deregister as FAILED — no third incarnation
    assert dep.ps.wait_for_job(job_id, timeout=120)
    assert rec.restarts == 1
    # and it never wrote a completed history (the run was cut short)
    from kubeml_tpu.api.errors import KubeMLException
    try:
        h = client.v1().histories().get(job_id)
        assert len(h.data.train_loss) < epochs
    except KubeMLException:
        pass  # no history at all is the expected common case


def test_sigterm_preemption_reschedules_without_budget(standalone_stack,
                                                       tmp_home):
    """Preemption grace end-to-end: SIGTERM the standalone child mid-job
    (the platform's eviction notice). The jobserver's handler drains the
    in-flight round, writes a round-granular checkpoint, posts
    /preempted to the PS and exits; the watchdog reschedules WITHOUT
    consuming the crash-restart budget — proven by max_restarts=0, where
    a crash-path exit would fail the job instead. The rescheduled
    incarnation resumes at the round cursor and finishes with one
    continuous history carrying preemptions=1."""
    import signal

    dep, client, tmp_path = standalone_stack
    paths = write_blob_files(tmp_path, n_train=4000)
    client.v1().datasets().create(
        "blobs", paths["xtr"], paths["ytr"], paths["xte"], paths["yte"])

    epochs = 30
    req = TrainRequest(model_type="mlp", batch_size=16, epochs=epochs,
                       dataset="blobs", lr=0.05,
                       options=TrainOptions(default_parallelism=2, k=1,
                                            static_parallelism=True,
                                            max_restarts=0,
                                            checkpoint_every_rounds=8,
                                            goal_accuracy=200.0))
    job_id = client.v1().networks().train(req)

    rec = _kill_in_window(dep, tmp_home, job_id, epochs,
                          sig=signal.SIGTERM)

    # the record must be rescheduled, not failed: preemption counted,
    # restart budget untouched
    deadline = time.time() + 120
    while time.time() < deadline:
        with dep.ps._jobs_lock:
            alive = dep.ps.jobs.get(job_id)
        if alive is None or rec.preemptions >= 1:
            break
        time.sleep(0.1)
    assert rec.preemptions == 1, "PS never saw the /preempted grace post"
    assert rec.restarts == 0, "preemption must not consume max_restarts"

    history = wait_history(client, job_id, timeout=300)
    assert len(history.data.train_loss) == epochs
    assert history.data.preemptions == 1
    assert history.data.restarts == 0
    assert dep.ps.wait_for_job(job_id, timeout=60)


def test_standalone_jobs_with_parent_accelerator_mesh_is_an_error():
    """One process per chip: a parent that built an accelerator mesh
    holds the chip its job children need — the combination is refused
    at start-up with a message that names it (a CPU mesh, which is only
    mirrored into the children as a device count, stays legal)."""
    import types

    import numpy as np

    from kubeml_tpu.control.ps import ParameterServer
    chip = types.SimpleNamespace(platform="tpu")
    mesh = types.SimpleNamespace(devices=np.array([chip], dtype=object))
    with pytest.raises(ValueError, match="one process per chip"):
        ParameterServer(mesh=mesh, standalone_jobs=True)
