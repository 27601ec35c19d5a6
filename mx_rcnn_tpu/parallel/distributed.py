"""Multi-host (multi-process) runtime initialization.

The reference scales past one host with MXNet KVStore ``dist_sync`` on a
ps-lite parameter server: ``tools/launch.py`` spawns scheduler/server/worker
processes wired by env vars, workers push gradients and pull weights each
iteration (SURVEY.md §3.8).  The TPU-native equivalent has no server role
at all: every host runs the same program, :func:`initialize` wires them
into one jax runtime (coordination service + global device view), and the
gradient all-reduce is an XLA collective over ICI/DCN inside the jitted
step.  Synchronous and deterministic — ``dist_sync`` semantics with no
push/pull machinery.

Launch parity:

  reference: python tools/launch.py -n 4 ... python train_end2end.py --kv-store dist_sync
  here:      srun/gcloud per host: python train.py --config r101_coco
             (TPU pods: env markers naming several hosts trigger
             autodetecting jax.distributed.initialize(); CPU/GPU clusters:
             pass coordinator/rank/count explicitly or via
             JAX_COORDINATOR_ADDRESS / JAX_PROCESS_ID / JAX_NUM_PROCESSES)

The data path is the GLOBAL-schedule design (data/loader.py): every host
keeps the full roidb, derives the identical global batch schedule
(shuffle order, orientation buckets, flip draws), and decodes only its
rank's rows of each global batch — lockstep per-step collectives by
construction, with no per-host roidb slicing to desync them.
:func:`mx_rcnn_tpu.parallel.shard_batch` then assembles each host's rows
into the global device array.  Together with this module that is the
complete multi-host story.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

log = logging.getLogger("mx_rcnn_tpu")


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join the multi-host runtime — and on one host, return at once.

    Joins when told to (arguments, or JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID) or when the environment names
    SEVERAL hosts (``TPU_WORKER_HOSTNAMES`` with more than one entry, or a
    ``MEGASCALE_COORDINATOR_ADDRESS``); then every failure is fatal —
    swallowing one would split-brain the job into N independent
    "process 0" runs clobbering one shared workdir.

    A single host never calls ``jax.distributed.initialize()``: a TPU VM
    sets ``CLOUD_TPU_TASK_ID`` / a one-entry ``TPU_WORKER_HOSTNAMES`` on
    single-host machines too, and jax's argument-less autodetection then
    asks the cloud metadata server — on a machine without network that
    is a wait or an error before the first step, for nothing.  Must run
    before the first device query in the process.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    env_n = os.environ.get("JAX_NUM_PROCESSES")
    n = num_processes if num_processes is not None else (
        int(env_n) if env_n else None
    )
    env_id = os.environ.get("JAX_PROCESS_ID")
    pid = process_id if process_id is not None else (
        int(env_id) if env_id else None
    )
    explicit = coordinator_address is not None or (n is not None and n > 1)
    hosts = [
        h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
        if h.strip()
    ]
    tpu_pod = len(hosts) > 1 or bool(
        os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
    )
    if not explicit and not tpu_pod:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=n,
        process_id=pid,
    )
    log.info(
        "distributed runtime up: process %d/%d, %d local + %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )


def is_primary() -> bool:
    """True on the host that owns shared side effects (checkpoint
    writes, metric journals, progress logging).  Process 0 by
    convention — trivially True single-process, and stable for the
    life of the runtime once :func:`initialize` has run.  Call sites
    gate on this instead of comparing ``jax.process_index()`` inline
    so the convention lives in exactly one place."""
    return jax.process_index() == 0


def describe_plan(plan) -> str:
    """One-line placement summary for run-start logs (all hosts see the
    SAME plan by construction — it is a pure function of cfg + mesh, so
    logging it per host doubles as a cheap lockstep sanity check in
    multi-host stdouts)."""
    mesh = plan.mesh
    if mesh is None:
        return "plan: single-device (no mesh)"
    return (
        f"plan: mesh {dict(mesh.shape)} over {jax.process_count()} "
        f"process(es), {len(plan.rules)} partition rules, "
        f"accum_steps={plan.accum_steps}, "
        f"steps_per_call={plan.steps_per_call}, "
        f"spatial={plan.spatial}, "
        f"step={'shard_map' if plan.use_shard_map else 'jit+gspmd'}"
    )
