"""chip_smoke.py on the CPU: each phase at a reduced size with the Pallas
kernels in interpret mode, and the script's refusal to run off a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from conftest import run_with_devices

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_kernel_check_reduced(dtype):
    res = chip_smoke.paged_kernel_check(
        batch=3, q_heads=4, kv_heads=2, head_dim=16, page_size=4,
        pages_per_seq=3, dtype=dtype)
    assert (res[f"paged_kernel_{dtype}_max_rel_err"]
            <= chip_smoke.PAGED_TOL[dtype])


def test_flash_check_reduced():
    res = chip_smoke.flash_check(batch=1, seq=256, q_heads=4, kv_heads=2,
                                 head_dim=64)
    assert max(res.values()) <= chip_smoke.FLASH_TOL


def test_serve_phase_reduced():
    res = chip_smoke.serve_phase(layers=2, requests=3, prompt_len=12,
                                 new_tokens=4, page_size=4, prefill_chunk=8,
                                 full=False)
    assert res["requests_completed"] == 3
    assert res["tokens_generated"] == 12


def test_train_phase_reduced():
    res = chip_smoke.train_phase(layers=1, batch=2, seq=128, steps=2,
                                 full=False)
    assert len(res["losses"]) == 2
    assert res["first_loss_abs_diff"] <= chip_smoke.LOSS_ATOL


def test_four_chip_phase_reduced():
    out = run_with_devices(f"""
import sys
sys.path.insert(0, {str(REPO)!r})
import chip_smoke
res = chip_smoke.four_chip_phase(layers=1, batch=4, seq=64, steps=2,
                                 full=False)
assert res["w_in_devices"] == [0, 1, 2, 3], res
assert res["w_in_shard_shape"] != res["w_in_shape"], res
print("OK")
""", n_devices=4)
    assert out.strip().endswith("OK")


def test_four_chip_phase_refuses_one_device():
    with pytest.raises(chip_smoke.SmokeFailure, match="need 4"):
        chip_smoke.four_chip_phase(layers=1, batch=4, seq=64, steps=1,
                                   full=False)


def test_script_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_placed_from_outside(monkeypatch):
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
