"""Data pipeline (prefetch, placement) and launcher CLIs (subprocess smoke)."""
import os
import subprocess
import sys

import numpy as np

from repro.data.pipeline import ShardedPipeline, synthetic_lm_stream


def test_synthetic_stream_shapes_and_structure():
    it = synthetic_lm_stream(vocab=128, batch=4, seq=16, seed=0)
    b = next(it)
    assert b["tokens"].shape == (4, 16) and b["labels"].shape == (4, 16)
    # next-token relationship holds
    b2 = next(it)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    # learnable structure: token marginal is far from uniform
    flat = np.concatenate([b["tokens"].ravel(), b2["tokens"].ravel()])
    assert len(np.unique(flat)) < 100


def test_pipeline_prefetch_and_close():
    it = (dict(x=np.full((2, 2), i)) for i in range(5))
    pipe = ShardedPipeline(it, prefetch=2)
    got = [int(b["x"][0, 0]) for b in pipe]
    assert got == list(range(5))
    pipe.close()


def _run_cli(mod, *args, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-m", mod, *args],
                         capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-1500:]
    return out.stdout


def test_train_cli_local():
    out = _run_cli("repro.launch.train", "--arch", "tiny-s", "--steps", "12",
                   "--batch", "4", "--seq", "32")
    assert "params" in out and "loss" in out


def test_serve_cli():
    out = _run_cli("repro.launch.serve", "--arch", "tiny-s", "--requests", "3",
                   "--max-new", "4")
    assert "served 3/3" in out


def test_serve_engine_builds_published_widths():
    from repro.launch.serve import engine_config, engine_parser

    cfg = engine_config(engine_parser().parse_args(["--arch", "qwen1.5-0.5b"]))
    assert (cfg.name, cfg.n_layers, cfg.d_model, cfg.dtype) == \
        ("qwen1.5-0.5b", 24, 1024, "bfloat16")
    small = engine_config(engine_parser().parse_args(
        ["--arch", "qwen1.5-0.5b", "--reduced"]))
    assert small.name == "qwen1.5-0.5b-smoke" and small.d_model < cfg.d_model


def test_serve_engine_refuses_what_it_cannot_serve():
    import pytest

    from repro.launch.serve import engine_config, engine_parser

    with pytest.raises(SystemExit, match="plain decoder"):
        engine_config(engine_parser().parse_args(["--arch", "seamless-m4t-large-v2"]))
    with pytest.raises(SystemExit, match="do not fit"):
        engine_config(engine_parser().parse_args(["--arch", "qwen1.5-0.5b"]),
                      device_bytes=2**30)


def test_compile_cache_dir(monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.serve import REPO_ROOT, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        enable_compile_cache()          # JAX reads the variable itself
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == os.path.join(REPO_ROOT,
                                                                    ".jax_cache")
        assert os.path.exists(os.path.join(REPO_ROOT, "chip_smoke.py"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_chip_smoke_refuses_cpu():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout
