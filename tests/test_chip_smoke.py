"""chip_smoke.py off the chip: it must refuse to run, and its phase
functions — the same ones the chip runs at full width — must pass tiny on
the CPU mesh.  Plus the compile-cache helper they all share."""

import os
import subprocess
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

from mpi4dl_tpu.compat import ensure_compilation_cache  # noqa: E402

# 256² is the smallest image whose 2x2 tiles stay wider than the halo down
# the whole spatial region of a 3-layer AmoebaNet.
TINY = dict(num_layers=3, num_filters=16, image_size=256, num_classes=10)


def test_chip_smoke_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert "no CPU path" in proc.stderr


def test_one_chip_trainer_tiny(devices8):
    out = chip_smoke.one_chip_trainer(steps=2, **TINY)
    assert out["final_step"] == 2 and len(out["losses"]) == 2
    assert out["state_devices"] == 1


def test_four_chip_spatial_tiny(devices8):
    """Leg A on four virtual devices: the 2x2 spatial run agrees with the
    one-device run step for step (raises SmokeFailure otherwise)."""
    chip_smoke.four_chip_spatial(steps=2, **TINY)


def test_compare_losses_rejects_a_wrong_loss():
    ref = {"losses": [2.0, 1.9]}
    chip_smoke.compare_losses("same", {"losses": [2.001, 1.9]}, ref)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_losses("off", {"losses": [2.2, 1.9]}, ref)


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_helper_leaves_a_set_variable_alone(monkeypatch,
                                                  cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    assert ensure_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir == "sentinel"


def test_cache_helper_fixed_path_in_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    path = ensure_compilation_cache()
    assert path == os.path.join(_REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
