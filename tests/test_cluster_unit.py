"""Fast, spawn-free coverage of the cluster subsystem's pure parts: env
construction, worker-result parsing/aggregation, the BENCH report shape,
and the subprocess error contract (exit codes, timeouts)."""
import json

import pytest

from _mp_helpers import SRC
from repro import _flags
from repro.bench import report as bench_report
from repro.bench.subproc import SubprocessError, resolve_timeout, \
    run_subprocess
from repro.cluster import local, report as crep, runtime
from repro.cluster.worker import RESULT_PREFIX, workload_argv
from repro.cluster.cli import workload_namespace


# ---------------------------------------------------------------------------
# env construction (the one helper every spawner shares)
# ---------------------------------------------------------------------------


def test_cluster_env_wires_coordinator_and_devices(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    env = _flags.cluster_env(2, SRC, coordinator="127.0.0.1:1234",
                             num_processes=4, process_id=3)
    assert env[_flags.ENV_COORD] == "127.0.0.1:1234"
    assert env[_flags.ENV_NUM_PROCS] == "4"
    assert env[_flags.ENV_PROC_ID] == "3"
    # last-flag-wins: worker count appended AFTER the ambient CI count
    assert env["XLA_FLAGS"].endswith(
        "--xla_force_host_platform_device_count=2")
    assert "device_count=8" in env["XLA_FLAGS"]
    assert env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] == "gloo"
    assert env["PYTHONPATH"].startswith(SRC)


def test_cluster_env_respects_explicit_collectives(monkeypatch):
    monkeypatch.setenv("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "mpi")
    env = _flags.cluster_env(1, SRC, coordinator="h:1", num_processes=2,
                             process_id=0)
    assert env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] == "mpi"


@pytest.mark.parametrize("make_env", [
    lambda: _flags.subprocess_env(2, SRC),
    lambda: _flags.cluster_env(2, SRC, coordinator="h:1", num_processes=2,
                               process_id=1),
], ids=["subprocess_env", "cluster_env"])
def test_child_env_pins_cpu_platform(monkeypatch, make_env):
    # a parent holding an accelerator must not hand it to its CPU ranks
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert make_env()["JAX_PLATFORMS"] == "cpu"


def test_runtime_from_env_roundtrip(monkeypatch):
    for v in (_flags.ENV_COORD, _flags.ENV_NUM_PROCS, _flags.ENV_PROC_ID):
        monkeypatch.delenv(v, raising=False)
    assert runtime.from_env() is None
    monkeypatch.setenv(_flags.ENV_COORD, "127.0.0.1:9")
    with pytest.raises(RuntimeError, match="partial cluster environment"):
        runtime.from_env()
    monkeypatch.setenv(_flags.ENV_NUM_PROCS, "2")
    monkeypatch.setenv(_flags.ENV_PROC_ID, "1")
    cfg = runtime.from_env()
    assert cfg == runtime.ClusterConfig("127.0.0.1:9", 2, 1)


def test_workload_argv_roundtrips_through_parser():
    import argparse

    from repro.cluster.worker import add_workload_args
    args = workload_namespace(grid="4x2", neurons_per_column=75, steps=33,
                              shards=8, exchange="halo", ckpt="/tmp/c.npz")
    ap = argparse.ArgumentParser()
    add_workload_args(ap)
    args2 = ap.parse_args(workload_argv(args))
    assert vars(args2) == vars(args)


# ---------------------------------------------------------------------------
# worker-result parsing + aggregation
# ---------------------------------------------------------------------------


def _result(proc, nprocs=2, sig="ab" * 32, wall=1.0, **kw):
    r = dict(proc=proc, nprocs=nprocs, shards=4, t0=0, steps=50,
             exchange="allgather", placement="block", local_devices=2,
             wall_s=wall, spikes=123, rate_hz=10.5, raster_sig=sig,
             phase_a_s=0.2, exchange_s=0.1, phase_b_s=0.3)
    r.update(kw)
    return r


def _stdout(result):
    return ("some jax warning\n" + RESULT_PREFIX + json.dumps(result)
            + "\ntrailing noise\n")


def test_parse_worker_outputs_orders_by_proc():
    outs = [_stdout(_result(1)), _stdout(_result(0))]
    res = crep.parse_worker_outputs(outs)
    assert [r["proc"] for r in res] == [0, 1]


def test_parse_worker_outputs_rejects_missing_result():
    with pytest.raises(ValueError, match="exactly one"):
        crep.parse_worker_outputs(["no result line here"])


def test_summarize_point_takes_max_wall_and_phases():
    row = crep.summarize_point([_result(0, wall=1.0, exchange_s=0.1),
                                _result(1, wall=2.5, exchange_s=0.9)])
    assert row["wall_s"] == 2.5
    assert row["exchange_s"] == 0.9
    assert len(row["per_proc"]) == 2


def test_summarize_point_rejects_diverging_rasters():
    with pytest.raises(ValueError, match="diverge"):
        crep.summarize_point([_result(0, sig="aa" * 32),
                              _result(1, sig="bb" * 32)])


def test_summarize_point_rejects_missing_proc():
    with pytest.raises(ValueError, match="expected results from procs"):
        crep.summarize_point([_result(0), _result(0)])


def test_scaling_report_is_bench_schema_valid():
    rows = [crep.summarize_point([_result(0, nprocs=1)]),
            crep.summarize_point([_result(0), _result(1, wall=2.0)])]
    rep = crep.scaling_report(rows, dict(quick=True, nprocs=[1, 2]))
    assert bench_report.validate(rep) == []
    assert rep["deterministic"]["identical_across_procs"] is True
    assert rep["wall"]["p1_wall_s"] == 1.0
    assert rep["wall"]["p2_wall_s"] == 2.0
    assert rep["wall"]["p2_exchange_s"] == 0.1
    assert rep["extra"]["points"][1]["per_proc"][1]["proc"] == 1


# ---------------------------------------------------------------------------
# subprocess error contract (shared by tests/bench/cluster spawners)
# ---------------------------------------------------------------------------


def test_run_subprocess_surfaces_exit_code():
    with pytest.raises(SubprocessError) as ei:
        run_subprocess("import sys; sys.exit(3)", timeout=60)
    assert ei.value.returncode == 3
    assert "exit code 3" in str(ei.value)


def test_run_subprocess_timeout_mentions_budget():
    with pytest.raises(SubprocessError) as ei:
        run_subprocess("import time; time.sleep(60)", timeout=1)
    assert ei.value.returncode is None
    assert "timed out after" in str(ei.value)


def test_resolve_timeout_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_SUBPROC_TIMEOUT", "123.5")
    assert resolve_timeout(None) == 123.5
    assert resolve_timeout(7.0) == 7.0


def test_launch_rejects_bad_nprocs():
    with pytest.raises(ValueError):
        local.launch(["-c", "pass"], nprocs=0)


def test_free_port_is_bindable_int():
    p = local.free_port()
    assert isinstance(p, int) and 0 < p < 65536
