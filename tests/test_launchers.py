"""CLI launcher smoke tests: the actual entry points users run, exercised
in subprocesses (fresh jax init per scenario)."""
import os

import pytest

from _mp_helpers import SRC, run_with_devices


@pytest.mark.slow
def test_snn_cli_dense_and_event(tmp_path):
    out = run_with_devices(
        "import sys; sys.argv=['snn','--grid','1x1',"
        "'--neurons-per-column','200','--synapses','20','--steps','80'];"
        "from repro.launch.snn import main; main()", 1)
    assert "done at t=80" in out
    out = run_with_devices(
        "import sys; sys.argv=['snn','--grid','1x1',"
        "'--neurons-per-column','200','--synapses','20','--steps','80',"
        "'--delivery','event'];"
        "from repro.launch.snn import main; main()", 1)
    assert "done at t=80" in out and "saturated 0" in out


@pytest.mark.slow
def test_snn_cli_event_distributed_with_checkpoint(tmp_path):
    """--delivery event is a first-class citizen of the sharded launcher:
    shards>1, halo exchange, checkpoint write + resume."""
    code = (
        "import sys; sys.argv=['snn','--grid','2x1',"
        "'--neurons-per-column','100','--synapses','20','--steps','60',"
        "'--shards','2','--exchange','halo','--delivery','event',"
        f"'--ckpt-dir',{str(tmp_path)!r},'--ckpt-every','30'];"
        "from repro.launch.snn import main; main()")
    out = run_with_devices(code, 2)
    assert "done at t=60" in out
    assert os.path.exists(os.path.join(str(tmp_path), "ckpt_60.npz"))
    # resume from the event-mode checkpoint
    code2 = code.replace("'--steps','60'", "'--steps','30'")
    out2 = run_with_devices(code2, 2)
    assert "resumed at t=60" in out2


@pytest.mark.slow
def test_snn_cli_distributed_with_checkpoint(tmp_path):
    code = (
        "import sys; sys.argv=['snn','--grid','2x1',"
        "'--neurons-per-column','100','--synapses','20','--steps','60',"
        "'--shards','2','--exchange','halo',"
        f"'--ckpt-dir',{str(tmp_path)!r},'--ckpt-every','30'];"
        "from repro.launch.snn import main; main()")
    out = run_with_devices(code, 2)
    assert "done at t=60" in out
    assert os.path.exists(os.path.join(str(tmp_path), "ckpt_60.npz"))
    # resume
    code2 = code.replace("'--steps','60'", "'--steps','30'")
    out2 = run_with_devices(code2, 2)
    assert "resumed at t=60" in out2


@pytest.mark.slow
def test_train_cli_smoke():
    out = run_with_devices(
        "import sys; sys.argv=['train','--arch','qwen3-0.6b','--smoke',"
        "'--steps','6','--batch','2','--seq','32'];"
        "from repro.launch.train import main; main()", 1, timeout=900)
    assert "'steps': 6" in out


@pytest.mark.slow
def test_serve_cli_smoke():
    out = run_with_devices(
        "import sys; sys.argv=['serve','--arch','rwkv6-1.6b','--smoke',"
        "'--requests','2','--batch','2','--max-new','4','--s-max','32'];"
        "from repro.launch.serve import main; main()", 1, timeout=900)
    assert "[serve] 2 requests" in out


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, where):
    """chip_smoke.py exits non-zero and prints no result line when JAX
    finds no TPU, and when it stands outside a checkout."""
    import shutil
    import subprocess
    import sys
    script = os.path.join(os.path.dirname(SRC), "chip_smoke.py")
    if where == "script_alone":
        script = shutil.copy(script, tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "FAILED" in out.stderr


@pytest.mark.parametrize("env_dir", [None, "/somewhere/cache"],
                         ids=["checkout", "env"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """The cache follows JAX_COMPILATION_CACHE_DIR when set (jax reads it
    itself) and is <checkout>/.jax_cache otherwise — a fixed path.  Source
    paths in kernel locations lose the checkout prefix, so the key is the
    same wherever the checkout lies."""
    import re

    import jax

    from repro import compile_cache
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV, env_dir)
    prev = jax.config.jax_compilation_cache_dir
    prev_re = jax.config.jax_hlo_source_file_canonicalization_regex
    try:
        got = compile_cache.enable()
        if env_dir is None:
            assert got == os.path.join(os.path.dirname(SRC), ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
        kernel = os.path.join(SRC, "repro", "kernels", "stdp.py")
        assert re.sub(jax.config.jax_hlo_source_file_canonicalization_regex,
                      "", kernel) == os.path.join("src", "repro", "kernels",
                                                  "stdp.py")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          prev_re)


@pytest.mark.slow
def test_dryrun_cli_one_cell():
    """The real dry-run driver end to end on the cheapest cell (its own
    XLA_FLAGS line forces 512 devices inside the subprocess)."""
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "rwkv6-1.6b", "--shape", "long_500k", "--single-pod-only"],
        capture_output=True, text=True, env=env, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[dryrun] OK" in out.stdout
