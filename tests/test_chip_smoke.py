"""chip_smoke.py at a tiny size on the CPU: the phases' own reference
checks pass, the script refuses to run without a TPU, and the compile
cache lands where the entry points put it."""
import json

import jax
import numpy as np
import pytest

import chip_smoke
from repro.common import compile_cache
from repro.core.client import ROS2Client


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "[device] platform=cpu" in out
    for line in out.splitlines():        # no result line was printed
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_ingest_checkpoint_placement_phases_tiny():
    dev = jax.devices()[0]
    c = ROS2Client(mode="dpu", transport="rdma")
    try:
        params, opt, losses = chip_smoke.ingest_train_phase(
            c, arch="tiny-gemma-7b", n_tokens=20_000, batch=4, seq=32,
            steps=2, seed=3)
        assert len(losses) == 2 and np.isfinite(losses).all()
        state = {"params": params, "opt": opt}
        assert chip_smoke.checkpoint_phase(c, state, 2) > 0
        host = [np.asarray(x) for x in jax.tree.leaves(params)]
        assert chip_smoke.placement_phase(c, host, dev,
                                          slot_bytes=256 << 10) >= 1
    finally:
        c.close()


def test_ec_phase_tiny():
    out = chip_smoke.ec_phase(nbytes=8 << 20, seed=5, samples=2)
    assert out["stripes"] == 8
    assert out["delta_writes"] >= 1 and out["reconstructions"] >= 1
    assert out["rebuilt_cells"] >= 1 and out["scrub_checks"] >= 8


def test_smoke_checks_catch_a_wrong_result():
    with pytest.raises(chip_smoke.SmokeError):
        chip_smoke.check(False, "wrong")
    a = np.array([np.nan], np.float32)
    b = a.view(np.uint32) ^ np.uint32(1)
    assert chip_smoke.same_bits(a, a.copy())
    assert not chip_smoke.same_bits(a, b.view(np.float32))


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                 cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.parent.joinpath(
        "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_env_wins(monkeypatch, cache_dir_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
