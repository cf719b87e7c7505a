"""Stdlib-only env-flag helpers, importable before any jax import.

Kept outside `repro.dist` because that package imports jax at load time;
launch scripts must be able to mutate XLA_FLAGS first.
"""
from __future__ import annotations

import os


def force_host_device_count(n: int, current: str | None = None) -> str:
    """XLA_FLAGS value forcing `n` logical host devices.

    APPENDS to the existing flags: XLA parses duplicated flags last-wins,
    so the count requested here overrides any ambient CI-level forced
    device count."""
    cur = os.environ.get("XLA_FLAGS", "") if current is None else current
    return f"{cur} --xla_force_host_platform_device_count={n}".strip()


# Platform of every child process started from `subprocess_env`.  Such
# children are CPU ranks by design (forced host devices, gloo collectives);
# pinning them keeps them off an accelerator their parent may hold — a
# chip belongs to one process at a time.
CHILD_PLATFORM = "cpu"


def subprocess_env(n_devices: int, src_path: str) -> dict:
    """Environment for a fresh-interpreter jax subprocess: the CPU
    platform, `n_devices` forced host devices (overriding any ambient
    forced count) and `src_path` prepended to PYTHONPATH so `repro`
    imports uninstalled.

    Shared by tests/_mp_helpers.py, repro.bench.subproc and
    repro.cluster.local so their subprocess environments cannot drift
    apart."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = CHILD_PLATFORM
    env["XLA_FLAGS"] = force_host_device_count(
        n_devices, env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = src_path + os.pathsep + env.get("PYTHONPATH", "")
    return env


# Coordinator wiring for multi-process (cluster) workers.  The names are
# repo-private so an ambient MPI/SLURM environment can never half-configure
# a worker; repro.cluster.runtime reads exactly these three.
ENV_COORD = "REPRO_CLUSTER_COORD"        # "host:port" of process 0
ENV_NUM_PROCS = "REPRO_CLUSTER_NPROCS"   # total process count
ENV_PROC_ID = "REPRO_CLUSTER_PROC_ID"    # this worker's rank


def cluster_env(n_devices: int, src_path: str, *, coordinator: str,
                num_processes: int, process_id: int,
                tuned: bool = False) -> dict:
    """`subprocess_env` plus the coordinator variables a cluster worker
    needs to join a `jax.distributed` job, and gloo CPU collectives so
    cross-process `ppermute`/`all_gather` work on the host backend (the
    variable is ignored by jax versions without the option and by non-CPU
    backends).  `tuned=True` overlays `tuned_host_env` (opt-in host-
    runtime tuning, A/B-comparable via the REPRO_TUNED_ENV marker)."""
    env = subprocess_env(n_devices, src_path)
    env[ENV_COORD] = coordinator
    env[ENV_NUM_PROCS] = str(num_processes)
    env[ENV_PROC_ID] = str(process_id)
    env.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
    if tuned:
        env.update(tuned_host_env())
    return env


# Known install locations of gperftools' tcmalloc on the distros the
# benchmark targets (the classic JAX-on-CPU launch-script preset: malloc
# pressure from host-side plan construction and per-step dispatch is real,
# and tcmalloc's thread caches are measurably faster than glibc's arena
# malloc for it).
TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib64/libtcmalloc.so.4",
    "/usr/lib64/libtcmalloc_minimal.so.4",
)

ENV_TUNED = "REPRO_TUNED_ENV"            # "1" when the preset is active


def find_tcmalloc() -> str | None:
    """First installed tcmalloc shared object, or None."""
    for p in TCMALLOC_CANDIDATES:
        if os.path.exists(p):
            return p
    return None


def tuned_host_env() -> dict:
    """Opt-in host-runtime tuning preset (cluster `--tuned-env`).

    LD_PRELOADs tcmalloc when installed (skipped silently otherwise — the
    preset must never break a launch), silences the large-alloc reporter
    and TF logging on the hot path.  Deliberately contains NO XLA flag
    that could alter compilation or numerics: the preset must keep the
    Table 1 invariant byte-exact, so it tunes only the host runtime
    around the compiled programs.  REPRO_TUNED_ENV=1 marks the worker so
    its result JSON records which A/B arm it ran in."""
    env = {
        "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
        "TF_CPP_MIN_LOG_LEVEL": "4",
        ENV_TUNED: "1",
    }
    tc = find_tcmalloc()
    if tc:
        prev = os.environ.get("LD_PRELOAD", "")
        env["LD_PRELOAD"] = f"{tc}:{prev}" if prev else tc
    return env
