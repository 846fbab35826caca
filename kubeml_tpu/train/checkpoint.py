"""Model checkpointing.

The reference has NO checkpoint/resume: weights live in RedisAI for the
job's lifetime and are deleted at job end (ml/pkg/train/util.go:211-244),
which makes its inference path vestigial (SURVEY.md §3.3). Here checkpoints
are first-class: the job saves its final (and optionally per-epoch) model
under $KUBEML_TPU_HOME/models/<job_id>/, and inference loads from there —
fixing the reference's weights-gone-after-training gap as SURVEY.md §7
prescribes.

Format: one .npz of flattened variable leaves keyed by '/'-joined tree
paths + a manifest.json (model name, dataset, dtypes). Self-describing —
restore needs no template pytree. A bfloat16 leaf is written as its 16
bits (uint16) and named in the manifest's `bfloat16_leaves`: numpy's
.npy format has no bfloat16 and would write a void type that comes back
as neither, and a model served in bfloat16 (models/deepseek_v2.py) must
reach the engine in it, bit for bit.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubeml_tpu.api.const import kubeml_home
from kubeml_tpu.api.errors import JobNotFoundError

logger = logging.getLogger("kubeml_tpu.checkpoint")

PyTree = Any


def _models_root() -> str:
    return os.path.join(kubeml_home(), "models")


def _flatten(variables: PyTree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(variables)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _unflatten(flat: Dict[str, np.ndarray]) -> PyTree:
    out: Dict[str, Any] = {}
    for key, arr in flat.items():
        node = out
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return out


def save_checkpoint(job_id: str, variables: PyTree, manifest: dict,
                    root: Optional[str] = None) -> str:
    root = root or _models_root()
    d = os.path.join(root, job_id)
    tmp = d + ".tmp"
    if os.path.isdir(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    flat = _flatten(variables)
    bf16 = sorted(k for k, a in flat.items() if a.dtype == jnp.bfloat16)
    for k in bf16:
        flat[k] = flat[k].view(np.uint16)
    np.savez(os.path.join(tmp, "weights.npz"), **flat)
    del flat
    manifest = dict(manifest, job_id=job_id, saved_at=time.time())
    if bf16:
        manifest["bfloat16_leaves"] = bf16
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    # crash-safe publish: at EVERY instant either the current dir or
    # .old holds a complete checkpoint (readers fall back to .old —
    # _resolve_dir), so a SIGKILL anywhere in this sequence costs at
    # most one save, never all recovery state. The .old cleanup happens
    # strictly inside the isdir(d) branch: in the fallback state
    # (d missing after a previous mid-publish crash) .old IS the only
    # good copy and must survive until the new dir is published.
    old = d + ".old"
    if os.path.isdir(d):
        if os.path.isdir(old):
            shutil.rmtree(old)
        os.rename(d, old)
    os.rename(tmp, d)
    shutil.rmtree(old, ignore_errors=True)
    return d


def _resolve_dir(job_id: str, root: Optional[str]) -> str:
    """The directory holding the job's newest DURABLE checkpoint.

    save_checkpoint's publish is two renames (current -> .old, then
    tmp -> current); a crash landing between them leaves no current
    directory but a fully-valid .old — falling back to it means a crash
    mid-checkpoint costs at most one epoch of recovery state, never all
    of it (the watchdog's restart eligibility and resume-from-self both
    read through here)."""
    d = os.path.join(root or _models_root(), job_id)
    if os.path.isfile(os.path.join(d, "manifest.json")):
        return d
    old = d + ".old"
    if os.path.isfile(os.path.join(old, "manifest.json")):
        return old
    return d  # missing everywhere: callers raise JobNotFound


def load_checkpoint(job_id: str, root: Optional[str] = None
                    ) -> Tuple[PyTree, dict]:
    # fast-fail the common not-found case BEFORE the retry loop: a job
    # that never checkpointed has neither directory, and no amount of
    # publish-race retrying will conjure one — without this check every
    # watchdog restart-eligibility probe and cold resume_from paid the
    # 50 ms sleep-and-retry below just to learn "no such checkpoint"
    base = os.path.join(root or _models_root(), job_id)
    if not os.path.isdir(base) and not os.path.isdir(base + ".old"):
        raise JobNotFoundError(job_id)
    # one retry on read failure: a cross-process reader that resolved
    # the .old fallback just before the writer's final rmtree(old) can
    # catch a half-deleted directory — after the publish completes, the
    # current dir is valid again, so a single re-resolve recovers. A
    # checkpoint that is missing EVERYWHERE raises immediately (no
    # retry tax on the common not-found path).
    for attempt in (0, 1):
        d = _resolve_dir(job_id, root)
        if not os.path.isfile(os.path.join(d, "manifest.json")):
            if attempt:
                raise JobNotFoundError(job_id)
            # _resolve_dir's choice may have been deleted between the
            # resolve and this check (the same mid-publish race as
            # below) — re-resolve once before declaring not-found
            time.sleep(0.05)
            continue
        try:
            with open(os.path.join(d, "manifest.json")) as f:
                manifest = json.load(f)
            bf16 = set(manifest.get("bfloat16_leaves", ()))
            with np.load(os.path.join(d, "weights.npz")) as z:
                variables = _unflatten(
                    {k: z[k].view(jnp.bfloat16) if k in bf16 else z[k]
                     for k in z.files})
            return variables, manifest
        except (OSError, ValueError) as e:
            if attempt:
                raise
            logger.warning(
                "checkpoint read for %s raced a publish (%s); retrying",
                job_id, e)
            time.sleep(0.05)


class AsyncCheckpointer:
    """Background checkpoint writer — training never blocks on a save.

    `save()` snapshots the variables ON DEVICE (`jnp.copy` per leaf — a
    fast HBM copy, so the snapshot survives the engines' buffer donation
    of the live variables on the next round) and returns immediately; a
    single daemon worker performs the expensive part (full-model
    device→host readback plus the atomic directory publish) off the
    training thread. Pending saves are latest-wins per job id: if epochs outpace the writer, intermediate
    snapshots are dropped and the newest wins — each published checkpoint
    is always a complete, consistent epoch state.

    `wait()` fully drains the queue and any in-flight write, then raises
    the first error whose job never got a LATER successful save (a newer
    durable checkpoint supersedes an earlier transient failure) — call it
    before declaring a job finished. `close()` drains, stops the worker
    thread, and releases everything; the owning job must call it so a
    long-lived server does not accumulate idle writer threads, and so no
    background write is mid-publish at process exit.

    Lifecycle: one checkpointer per TrainJob (wait()/close() clear ALL
    latched errors, so sharing one instance across concurrent jobs would
    let one job's wait() swallow another's failure).

    Backlog bound: the latest-wins dict caps the queue at ONE pending
    snapshot per job — a round-granular cadence (checkpoint_every_rounds)
    outpacing a slow disk coalesces into the newest state instead of
    building an unbounded HBM backlog of device snapshots. Every
    coalesced (dropped) save is counted in `dropped_saves` and logged,
    so a persistently-starved writer is observable, and the counter is
    surfaced as the job's kubeml_job_checkpoint_drops gauge.
    """

    def __init__(self, root: Optional[str] = None):
        self.root = root
        self._cond = threading.Condition()
        self._pending: Dict[str, Tuple[PyTree, dict]] = {}
        self._in_flight_job: Optional[str] = None
        self._errors: Dict[str, BaseException] = {}
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self.dropped_saves = 0

    def save(self, job_id: str, variables: PyTree, manifest: dict) -> None:
        snap = jax.tree_util.tree_map(jnp.copy, variables)
        with self._cond:
            if self._closed:
                raise RuntimeError("AsyncCheckpointer is closed")
            if job_id in self._pending:
                self.dropped_saves += 1
                logger.info(
                    "checkpoint save for %s coalesced into a newer "
                    "snapshot (writer behind; %d dropped so far)",
                    job_id, self.dropped_saves)
            self._pending[job_id] = (snap, manifest)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="kubeml-ckpt", daemon=True)
                self._thread.start()
            self._cond.notify_all()

    def wait(self) -> None:
        with self._cond:
            self._cond.wait_for(
                lambda: not self._pending and self._in_flight_job is None)
            if self._errors:
                job_id, err = next(iter(self._errors.items()))
                for other_job, other in self._errors.items():
                    if other_job != job_id:
                        # aggregated into the log, not the raise: a second
                        # job's failure must stay observable even though
                        # only the first latched error propagates
                        logger.error(
                            "checkpoint save for job %s also failed: %s",
                            other_job, other)
                self._errors.clear()
                raise err

    def close(self) -> None:
        """Drain outstanding writes and stop the worker. Idempotent.
        Errors don't propagate from here — call wait() first when they
        must — but any still-latched failure is logged so it is never
        silently lost."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        with self._cond:
            for job_id, err in self._errors.items():
                logger.error(
                    "checkpoint save for job %s failed (discarded at "
                    "close): %s", job_id, err)
            self._errors.clear()

    def _run(self) -> None:
        while True:
            with self._cond:
                self._cond.wait_for(
                    lambda: bool(self._pending) or self._closed)
                if not self._pending:  # closed and drained
                    return
                job_id, (snap, manifest) = next(iter(self._pending.items()))
                del self._pending[job_id]
                self._in_flight_job = job_id
            try:
                save_checkpoint(job_id, snap, manifest, root=self.root)
                with self._cond:  # durable newer save supersedes old error
                    self._errors.pop(job_id, None)
            except BaseException as e:  # surfaced by wait()
                with self._cond:
                    self._errors.setdefault(job_id, e)
            finally:
                # drop the model-sized snapshot before idling: the loop
                # frame must not retain a full device copy between saves
                snap = manifest = None
                with self._cond:
                    self._in_flight_job = None
                    self._cond.notify_all()


def mark_checkpoint_completed(job_id: str, root: Optional[str] = None
                              ) -> None:
    """Stamp the published manifest `completed=True`, weights untouched.

    Used when the last periodic save already captured the final model
    state (so rewriting the weights would be redundant): the flag tells
    a crash-recovery resume that the job's epochs are DONE — a process
    killed between its final save and its /finish notification must
    finish immediately on restart, not retrain. saved_at is preserved so
    manifest-stamp caches (the PS infer cache) stay valid."""
    path = os.path.join(_resolve_dir(job_id, root), "manifest.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["completed"] = True
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f)
    os.replace(tmp, path)


def checkpoint_saved_at(job_id: str, root: Optional[str] = None
                        ) -> Optional[float]:
    """The manifest's saved_at stamp, or None when absent/unreadable.

    The cheap freshness probe for caches: save_checkpoint writes a
    monotonically newer time.time() into every manifest, so comparing
    saved_at is immune to filesystem mtime granularity.

    Reads retry once on failure (same publish race as load_checkpoint):
    a transient half-deleted .old must not make the crash watchdog
    spuriously deem a job checkpoint-less — and therefore restart-
    ineligible — at the exact moment a valid checkpoint exists."""
    base = os.path.join(root or _models_root(), job_id)
    for attempt in (0, 1):
        d = _resolve_dir(job_id, root)
        try:
            with open(os.path.join(d, "manifest.json")) as f:
                return json.load(f).get("saved_at")
        except (OSError, ValueError):
            if attempt:
                return None
            # missing EVERYWHERE (checked against the primary and .old
            # paths, not the possibly-stale resolved one) is the common
            # no-checkpoint answer — no retry tax; anything else could
            # be the mid-publish race, so re-resolve once
            if not os.path.isdir(base) and not os.path.isdir(base + ".old"):
                return None
            time.sleep(0.05)


def delete_checkpoint(job_id: str, root: Optional[str] = None) -> None:
    root = root or _models_root()
    d = os.path.join(root, job_id)
    for path in (d, d + ".old", d + ".tmp"):
        if os.path.isdir(path):
            shutil.rmtree(path)


def list_checkpoints(root: Optional[str] = None) -> list:
    root = root or _models_root()
    if not os.path.isdir(root):
        return []
    return sorted(j for j in os.listdir(root)
                  if os.path.isfile(os.path.join(root, j, "manifest.json")))
