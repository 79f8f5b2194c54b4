"""Multi-host SPMD bring-up (replacing the reference's cluster launchers:
paddle/scripts/cluster_train fabric/k8s scripts + etcd discovery).

One SPMD program spans all hosts: `init_distributed()` wires this process
into the global device mesh via `jax.distributed.initialize` (XLA handles
ICI within a slice and DCN across slices — no NCCL/gRPC/pserver plumbing).
Env contract kept close to the reference's (submit_local.sh.in / Flags.h:19
trainer_id / trainers):

  PADDLE_TRAINER_ID     — process index (0-based)
  PADDLE_TRAINERS       — total process count
  PADDLE_COORDINATOR    — host:port of process 0

Single-process multi-device needs none of this; tests simulate multi-chip
with --xla_force_host_platform_device_count."""

from __future__ import annotations

import os
from typing import Optional


def env_trainer_id() -> int:
    return int(os.environ.get("PADDLE_TRAINER_ID", "0"))


def env_trainer_count() -> int:
    return int(os.environ.get("PADDLE_TRAINERS", "1"))


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None):
    """Join the multi-host job. No-op for single-host jobs."""
    import jax

    num = num_processes if num_processes is not None else env_trainer_count()
    if num <= 1:
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator
        or os.environ.get("PADDLE_COORDINATOR", "127.0.0.1:8476"),
        num_processes=num,
        process_id=process_id if process_id is not None else env_trainer_id(),
    )
    return True


def global_mesh(axes=None):
    """Mesh over ALL processes' devices (jax.devices() is global after
    init_distributed)."""
    from ..mesh import make_mesh

    return make_mesh(axes)


def shard_reader(reader, trainer_id: Optional[int] = None,
                 trainer_count: Optional[int] = None):
    """Deterministic round-robin sample sharding per host process (the
    task-pull alternative is distributed.master)."""
    tid = trainer_id if trainer_id is not None else env_trainer_id()
    tc = trainer_count if trainer_count is not None else env_trainer_count()

    def reader_():
        for i, s in enumerate(reader()):
            if i % tc == tid:
                yield s

    return reader_
