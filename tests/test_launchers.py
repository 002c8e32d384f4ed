"""Launcher CLIs (launch/train.py, launch/serve.py) run end-to-end,
including the traced+sampled path with the Folding profile."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

ROOT = "/root/repo"


def _run(mod, args, timeout=560):
    env = {**os.environ, "PYTHONPATH": f"{ROOT}/src"}
    return subprocess.run(
        [sys.executable, "-m", mod, *args], capture_output=True, text=True,
        env=env, cwd=ROOT, timeout=timeout,
    )


def test_train_cli(tmp_path):
    r = _run("repro.launch.train",
             ["--arch", "mamba2-370m", "--steps", "12", "--batch", "4",
              "--seq", "32", "--workdir", str(tmp_path), "--trace",
              "--sample-hz", "200", "--checkpoint-every", "6"])
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert "loss" in r.stdout
    assert "checkpoints: [6, 12]" in r.stdout
    assert "trace:" in r.stdout
    assert "folded profile over 12 steps" in r.stdout
    assert (tmp_path / "trace.prv").exists()
    assert (tmp_path / "trace.chrome.json").exists()


def test_serve_cli(tmp_path):
    r = _run("repro.launch.serve",
             ["--arch", "recurrentgemma-9b", "--requests", "2",
              "--prompt-len", "16", "--gen", "8", "--trace",
              "--out", str(tmp_path)])
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert "tok/s" in r.stdout
    assert (tmp_path / "serve.prv").exists()


def test_serve_published_widths_cut_in_depth_only(capsys):
    """``--layers N`` serves the registry config at published widths in
    bf16, cut only in depth, and says so; no flag keeps reduced()."""
    from repro.configs import get_config
    from repro.launch import serve

    report = serve.run(["--layers", "1", "--requests", "2", "--slots", "2",
                        "--prompt-len", "8", "--gen", "4"])
    full = get_config("granite-8b")
    assert report["cfg"] == full.replace(num_layers=1)
    assert "1 of 36 layers (depth cut by 35)" in capsys.readouterr().out
    assert [len(o) for o in report["outputs"]] == [4, 4]
    with pytest.raises(SystemExit):
        serve.run(["--layers", "37"])


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    import jax

    from repro.launch import cache

    monkeypatch.setenv(cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_compile_cache_default_dir(monkeypatch, backend):
    import jax

    from repro.launch import cache

    monkeypatch.delenv(cache.CACHE_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = cache.use_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    if backend == "tpu":
        assert got == now == str(cache.REPO_CACHE)
        # one fixed directory inside the checkout
        assert cache.REPO_CACHE.name == ".jax_cache"
        assert (cache.REPO_CACHE.parent / "src" / "repro").is_dir()
    else:
        assert got is None and now == was
