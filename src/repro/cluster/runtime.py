"""`jax.distributed` bootstrap + host-gather helpers.

A worker joins the job from exactly three env variables (set by the
launcher — `repro._flags.cluster_env`) or from explicit arguments:

  REPRO_CLUSTER_COORD    "host:port" of process 0's coordinator service
  REPRO_CLUSTER_NPROCS   total process count
  REPRO_CLUSTER_PROC_ID  this worker's rank

`ensure_initialized()` is guarded three ways so single-process callers are
untouched: it is a no-op when the variables are absent, idempotent when
called twice, and must run before jax first initializes its backends
(call it at the top of `main()`, before any `jax.devices()`/`jnp` use).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax

from .._flags import ENV_COORD, ENV_NUM_PROCS, ENV_PROC_ID


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    coordinator: str
    num_processes: int
    process_id: int


def from_env() -> Optional[ClusterConfig]:
    """ClusterConfig from the REPRO_CLUSTER_* variables; None when not a
    cluster worker.  Half-set variables are an error, not a silent no-op —
    a worker that quietly ran single-process would deadlock its peers."""
    present = [v for v in (ENV_COORD, ENV_NUM_PROCS, ENV_PROC_ID)
               if os.environ.get(v)]
    if not present:
        return None
    if len(present) != 3:
        raise RuntimeError(
            f"partial cluster environment: have {present}, need all of "
            f"{[ENV_COORD, ENV_NUM_PROCS, ENV_PROC_ID]}")
    return ClusterConfig(coordinator=os.environ[ENV_COORD],
                         num_processes=int(os.environ[ENV_NUM_PROCS]),
                         process_id=int(os.environ[ENV_PROC_ID]))


_initialized = False


def ensure_initialized(cfg: Optional[ClusterConfig] = None) -> bool:
    """Join the distributed job described by `cfg` (default: env vars).

    Returns True when running multi-process-initialized, False for plain
    single-process callers.  Must be called before jax touches devices.
    """
    global _initialized
    if _initialized:
        return True
    cfg = cfg or from_env()
    if cfg is None:
        return False
    # CPU collectives for cross-process ppermute/all_gather come from
    # JAX_CPU_COLLECTIVES_IMPLEMENTATION, which `cluster_env` sets
    jax.distributed.initialize(coordinator_address=cfg.coordinator,
                               num_processes=cfg.num_processes,
                               process_id=cfg.process_id)
    _initialized = True
    return True


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_primary() -> bool:
    """True on the process that should own side effects (checkpoint
    writes, report files); all processes in a single-process job."""
    return jax.process_index() == 0


def is_distributed() -> bool:
    return jax.process_count() > 1


def gather(tree):
    """Host-local numpy copy of a tree of (possibly process-spanning)
    arrays.  A collective when multi-process — every process must call it
    with the same tree structure."""
    import numpy as np

    from ..dist import compat as dist_compat
    if jax.process_count() == 1:
        return jax.tree.map(np.asarray, tree)
    return dist_compat.process_allgather(tree)
