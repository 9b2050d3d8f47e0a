"""Tier-1 rehearsal of ``chip_smoke.py``: its phase functions at tiny
sizes on the CPU (Pallas in interpret mode, the 4-way mesh on the
conftest's virtual devices), and the script itself refusing to run
without a TPU — a failure, never a CPU fallback."""
import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke as cs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("phase", ["replay", "serve-flat", "serve-lanes",
                                   "mesh"])
def test_phases_rehearse_on_cpu(phase):
    if phase == "replay":
        out = cs.phase_replay(patches=2000, batch=8, capacity=1024,
                              chunk=128, interpret=True)
        assert out["lanes"] == 8 and out["chars"] > 0
    elif phase == "serve-flat":
        out = cs.phase_serve("flat", docs=24, shards=2, lanes=4, ticks=6,
                             events=48, compiled=False)
        assert out["lanes_verified"] + out["host_only"] == 24
        assert out["evictions"] > 0 and out["restores"] > 0
    elif phase == "serve-lanes":
        out = cs.phase_serve("rle-lanes-mixed", docs=8, shards=1, lanes=2,
                             ticks=3, events=16, compiled=False)
        assert out["lanes_verified"] + out["host_only"] == 8
    else:
        out = cs.phase_mesh(jax.devices()[:4], docs=8, patches=300)
        assert out["devices"] == 4 and out["docs"] == 8


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_fails_without_a_tpu(where, tmp_path):
    """No TPU means a non-zero exit and no contract line, both from the
    checkout and from a directory holding only the script."""
    cwd = REPO
    if where == "alone":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("env_dir", [None, "/some/cache"])
def test_compile_cache_is_placed_from_outside(env_dir, monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else a fixed path in the
    checkout — never a temp name."""
    from text_crdt_rust_tpu.utils import compile_cache as CC

    set_to = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_to.append((k, v)))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = env_dir or os.path.join(REPO, ".jax_cache")
    assert CC.enable_compile_cache() == want
    assert set_to == [("jax_compilation_cache_dir", want)]
