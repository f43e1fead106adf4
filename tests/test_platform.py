"""Platform guards: the chip job never runs on the CPU, emulation never
reaches for a chip, and the compile cache goes where it is placed."""
import os
import shutil
import subprocess
import sys

import pytest

from helpers import REPO, run_with_devices


def _run_smoke(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env=env)


def test_chip_smoke_refuses_the_cpu():
    proc = _run_smoke(REPO)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout
    # It stopped before generating any input.
    assert "[gen]" not in proc.stdout
    assert not [d for d in os.listdir(REPO)
                if d.startswith("chip-smoke-store-")]


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_emulated_children_run_on_the_cpu():
    out = run_with_devices("""
import os, jax
assert os.environ["JAX_PLATFORMS"] == "cpu"
print(jax.default_backend(), len(jax.devices()))
""", n_devices=2)
    assert out.split() == ["cpu", "2"]


def test_process_worker_refuses_a_chip_host(monkeypatch, tmp_path):
    import jax

    from repro.core.external_sort import ExternalSortPlan
    from repro.io.object_store import ObjectStore
    from repro.shuffle import procworker

    def no_spawn(*a, **k):
        raise AssertionError("spawned a child on a chip host")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(procworker.subprocess, "Popen", no_spawn)
    with pytest.raises(RuntimeError, match="CPU only"):
        procworker.ProcessWorker(
            "w0", store=ObjectStore(str(tmp_path)), bucket="b",
            plan=ExternalSortPlan(records_per_wave=1 << 10))


@pytest.mark.parametrize("placed", [None, "elsewhere"])
def test_compile_cache_placement(monkeypatch, tmp_path, placed):
    import jax

    from repro.core import compat

    before = jax.config.jax_compilation_cache_dir
    if placed is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
    else:
        want = str(tmp_path / placed)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compat.enable_compile_cache() == want
        # A placed cache is JAX's to read; nothing in code overrides it.
        assert jax.config.jax_compilation_cache_dir == (
            want if placed is None else before)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
