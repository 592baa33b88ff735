"""The platform decides: Pallas interpret mode, the chip smoke's device
check, and where the persistent compile cache lives."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core import precision
from repro.core.precision import MiragePolicy, get_policy, resolve_interpret
from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_policy_interpret_follows_cpu_backend():
    assert jax.default_backend() == "cpu"
    assert get_policy("mirage").interpret is None
    assert resolve_interpret(get_policy("mirage_rrns").interpret) is True
    assert resolve_interpret(False) is False


def test_policy_interpret_follows_tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(get_policy("mirage").interpret) is False
    with pytest.raises(ValueError, match="interpret mode"):
        resolve_interpret(True)
    with pytest.raises(ValueError, match="interpret mode"):
        MiragePolicy(mode="mirage_rrns", use_pallas=True, interpret=True)


def test_kernel_wrappers_default_to_the_platform():
    import inspect

    from repro.kernels import (bfp_quantize, flash_attention, mirage_gemm,
                               ops, rns_matmul, rrns_decode)
    fns = [bfp_quantize.bfp_fake_quant_pallas, mirage_gemm.mirage_gemm_pallas,
           rns_matmul.rns_matmul_pallas, rns_matmul.rns_matmul_pallas_channel,
           rrns_decode.rrns_decode_pallas, flash_attention.flash_attention,
           ops.rns_residue_matmul, ops.rns_group_matmul,
           ops.rns_group_matmul_channel]
    for fn in fns:
        sig = inspect.signature(fn)
        assert sig.parameters["interpret"].default is None, fn
    assert MiragePolicy().interpret is None
    assert precision.PAPER_POLICY.interpret is None


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def _load_chip_smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("phase", ["train", "pallas", "serve"])
def test_chip_smoke_phase_at_reduced_size(phase, restore_cache_dir):
    """The smoke's phases, with their checks, on CPU at the reduced config
    (interpret-mode kernels): what the chip run executes at full width."""
    getattr(_load_chip_smoke(), "phase_" + phase)(reduced=True)


def _run_smoke(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok") is not None:
                return True
        except (ValueError, AttributeError):
            continue
    return False


def test_chip_smoke_fails_without_tpu():
    proc = _run_smoke(REPO, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not _has_result_line(proc.stdout)


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_smoke(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no repro package" in proc.stderr
    assert not _has_result_line(proc.stdout)


def test_compile_cache_env_var_stands(monkeypatch, tmp_path,
                                      restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
