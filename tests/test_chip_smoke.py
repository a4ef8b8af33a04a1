"""chip_smoke.py rehearsed on the CPU, and the compile-cache rule.

The rehearsal is the whole script at the `tiny` preset: three real
processes (coordinator, worker, frontend), every request served over
HTTP — and then a non-zero exit *because* the worker's arrays sit on
the CPU, which is the one thing a chip-free run cannot satisfy.
"""

import json
import os
import pathlib
import subprocess
import sys

import jax

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_cpu_rehearsal_fails_only_on_the_platform_check():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)          # one CPU device, as one chip
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--model", "tiny"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    out = proc.stdout
    lines = out.strip().splitlines()
    assert proc.returncode != 0, out
    assert json.loads(lines[-1]) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}, out
    # everything else was exercised and held
    assert "requests answered over HTTP" in out, out
    assert "worker scrape:" in out and "decode_burst" in out, out
    # the chip-free processes stayed off jax at run time
    assert ('jax loaded: {"parent": false, "coordinator": false, '
            '"frontend": false}') in out, out
    failed = [ln for ln in lines if "FAILED" in ln]
    assert len(failed) == 1 and "not on a TPU" in failed[0], out


def _record_config_updates(monkeypatch) -> list:
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **kw: calls.append(a))
    return calls


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    from dynamo_tpu.cli_util import enable_compile_cache

    calls = _record_config_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert calls == []                  # jax reads the variable itself


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch):
    from dynamo_tpu.cli_util import enable_compile_cache

    calls = _record_config_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert enable_compile_cache() == want
    assert enable_compile_cache() == want          # never moves
    assert calls == [("jax_compilation_cache_dir", want)] * 2
    assert "DYN_COMPILE_CACHE" not in (REPO / "dynamo_tpu"
                                       / "cli_util.py").read_text()
