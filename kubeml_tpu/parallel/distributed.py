"""Multi-host / multi-slice distributed runtime.

The reference scales out with NCCL-free HTTP fan-out over Kubernetes pods
(SURVEY.md §2b: RedisAI blackboard data plane + JSON control plane). The
TPU-native equivalent is JAX's multi-controller runtime: every host runs
the same program, `jax.distributed` forms the cluster, and XLA collectives
ride ICI within a slice and DCN across slices. Nothing else in the
framework changes — the mesh abstracts the transport, so the same
KAvgEngine/TP/SP/PP/EP code paths run single-chip, single-slice, and
multi-slice.

Two entry points:

  initialize(...)        — join (or bootstrap) the multi-host cluster.
                           On Cloud TPU pods all arguments are discovered
                           from the metadata environment; off-TPU the
                           caller passes coordinator/num_processes/
                           process_id explicitly.
  make_multislice_mesh() — a (data, model, seq, stage, expert) mesh whose
                           device order is SLICE-MAJOR on the data axis:
                           lanes that differ only within a slice are
                           ICI-adjacent, and the data-parallel psum
                           decomposes into per-slice reduce (ICI) + a
                           small cross-slice phase (DCN) — the layout the
                           XLA multi-slice all-reduce pass expects.
                           Inner (model/seq/stage/expert) axes never
                           cross a slice boundary, keeping the
                           latency-sensitive TP/ring/pipeline collectives
                           on ICI.

The data-parallel semantics over DCN are identical to single-slice: the
K-avg weight average is one masked psum over the full `data` axis
(parallel/kavg.py), regardless of how many slices that axis spans.
"""

from __future__ import annotations

import collections
import logging
import os
from typing import Dict, List, Optional, Sequence

import jax
from jax.sharding import Mesh

from kubeml_tpu.parallel.mesh import make_mesh

logger = logging.getLogger("kubeml_tpu.distributed")

# Every env-var family that can make a process believe it belongs to a
# jax.distributed cluster — our own launcher vars plus everything
# initialize()/_cluster_env_present auto-detects (jax / megascale /
# TPU-pod / SLURM / OpenMPI). Kept HERE, next to the detection logic,
# so detection and scrubbing (control/ps.py strips these from
# standalone-job child envs) evolve together: a child inheriting its
# parent's rank re-joins the parent's cluster and hangs it.
CLUSTER_ENV_VARS = (
    "KUBEML_COORDINATOR_ADDRESS", "KUBEML_NUM_PROCESSES",
    "KUBEML_PROCESS_ID",
    "JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES", "JAX_PROCESS_ID",
    "MEGASCALE_COORDINATOR_ADDRESS", "MEGASCALE_NUM_SLICES",
    "MEGASCALE_SLICE_ID",
    "TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID",
    "SLURM_NTASKS", "SLURM_PROCID", "SLURM_JOB_ID",
    "OMPI_COMM_WORLD_SIZE", "OMPI_COMM_WORLD_RANK",
)


def _cluster_env_present() -> bool:
    """True when the environment indicates a MULTI-host cluster
    (jax.distributed auto-detects from these families). If so, a failed
    join must raise — proceeding single-process would train N independent
    model copies and report wrong results. Single-host values (e.g.
    TPU_WORKER_HOSTNAMES=localhost on a 1-host slice) don't count."""
    if os.environ.get("JAX_COORDINATOR_ADDRESS") \
            or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS") \
            or os.environ.get("KUBEML_COORDINATOR_ADDRESS"):
        return True
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hosts.split(",") if h.strip()]) > 1:
        return True
    for var in ("SLURM_NTASKS", "OMPI_COMM_WORLD_SIZE"):
        try:
            if int(os.environ.get(var, "1")) > 1:
                return True
        except ValueError:
            pass
    return False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the multi-host JAX cluster (idempotent).

    On Cloud TPU pod slices the launch environment names the cluster
    (the env-var families in CLUSTER_ENV_VARS) and JAX discovers the
    coordinator and process topology from it. For DCN-connected CPU/GPU
    hosts or manual bring-up, pass all three arguments (or the
    KUBEML_COORDINATOR_ADDRESS / _NUM_PROCESSES / _PROCESS_ID trio).
    Must be the FIRST JAX call in the process (jax.distributed's own
    contract): touching the backend first makes joining impossible, so
    this function deliberately makes no other JAX calls before the join.

    With explicit arguments, or an environment that names a multi-host
    cluster, a rendezvous failure raises — silently training N
    independent model copies would be wrong results, not degraded
    service. With NEITHER — no arguments, no KUBEML_COORDINATOR_ADDRESS,
    no cluster environment — this is a single-process run and returns
    WITHOUT calling jax.distributed.initialize() at all: on a TPU host
    with no network, JAX's own cluster detection may wait on a metadata
    server that is not there, and `kubeml serve` must start regardless.

    This replaces the reference's Kubernetes Service discovery + HTTP
    rendezvous (ml/pkg/api/const.go:4-14, ml/pkg/ps/job_pod.go:96-137):
    after initialize(), `jax.devices()` spans every chip in the cluster
    and collectives over any mesh built from them ride ICI/DCN.
    """
    is_init = getattr(jax.distributed, "is_initialized", None)
    if is_init is not None and is_init():
        return  # already part of a cluster
    # env-driven bring-up (tools/launch_distributed.py and manual
    # multi-host runs set these; explicit arguments win)
    if coordinator_address is None:
        coordinator_address = os.environ.get("KUBEML_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("KUBEML_NUM_PROCESSES"):
        num_processes = int(os.environ["KUBEML_NUM_PROCESSES"])
    if process_id is None and os.environ.get("KUBEML_PROCESS_ID"):
        process_id = int(os.environ["KUBEML_PROCESS_ID"])
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if not kwargs and not _cluster_env_present():
        logger.info("single-process run (no cluster named by arguments "
                    "or environment)")
        return
    # a named cluster must not silently degrade to one process: failures
    # propagate
    jax.distributed.initialize(**kwargs)
    logger.info("joined cluster: process %d/%d, %d devices",
                jax.process_index(), jax.process_count(),
                len(jax.devices()))


def group_by_slice(devices: Sequence,
                   n_slices: Optional[int] = None) -> List[List]:
    """Partition devices into ICI-connected groups (slices).

    Real TPU devices carry `slice_index`; hosts without it (CPU tests,
    single-slice) fall back to process_index, and `n_slices` forces an
    even contiguous split for emulating multi-slice layouts on virtual
    devices.
    """
    devices = list(devices)
    if n_slices is not None:
        if len(devices) % n_slices:
            raise ValueError(
                f"{len(devices)} devices not divisible into {n_slices} "
                "slices")
        per = len(devices) // n_slices
        return [devices[i * per:(i + 1) * per] for i in range(n_slices)]
    groups: Dict[int, List] = collections.defaultdict(list)
    for d in devices:
        sid = getattr(d, "slice_index", None)
        if sid is None:
            sid = getattr(d, "process_index", 0)
        groups[sid].append(d)
    sizes = {len(g) for g in groups.values()}
    if len(sizes) > 1:
        raise ValueError(f"uneven slices: {sorted(sizes)}")
    return [sorted(groups[sid], key=lambda d: d.id)
            for sid in sorted(groups)]


def make_multislice_mesh(n_model: int = 1, n_seq: int = 1, n_stage: int = 1,
                         n_expert: int = 1,
                         devices: Optional[Sequence] = None,
                         n_slices: Optional[int] = None) -> Mesh:
    """Build the standard 5-axis mesh over a multi-slice cluster.

    The full `data` axis spans all slices, slice-major: data lane
    d = s * data_per_slice + i maps to slice s, in-slice data lane i
    (data_per_slice = slice size / product of inner axes). Inner axes are
    filled within a slice (they must divide the slice size), so
    model/seq/stage/expert collectives never touch DCN.

    Degenerates to exactly `make_mesh(...)` ordering on one slice, so
    callers can use it unconditionally.
    """
    if devices is None:
        devices = jax.devices()
    slices = group_by_slice(devices, n_slices=n_slices)
    per_slice = len(slices[0])
    inner = n_model * n_seq * n_stage * n_expert
    if per_slice % inner:
        raise ValueError(
            f"slice size {per_slice} not divisible by inner axes product "
            f"{inner} ({n_model}x{n_seq}x{n_stage}x{n_expert}) — inner "
            "axes must not cross a slice boundary")
    data_per_slice = per_slice // inner
    return make_mesh(n_data=len(slices) * data_per_slice, n_model=n_model,
                     n_seq=n_seq, n_stage=n_stage, n_expert=n_expert,
                     devices=[d for s in slices for d in s])


def is_coordinator() -> bool:
    """True on the process that should run the control plane (serve the
    REST API, write history/checkpoints). Mirrors the reference's single
    controller deployment (SURVEY.md §1 L5) in the multi-controller
    runtime: exactly one process, the others only execute collectives."""
    return jax.process_index() == 0
