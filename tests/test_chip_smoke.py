"""chip_smoke.py's phases on the CPU at tiny sizes (kernels pinned to
interpret mode), its four-chip phase on 4 virtual CPU devices, and its
refusal to run anywhere but on a TPU inside the repository."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import smoke_config

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("qwen2-1.5b").with_(use_pallas_decode=True,
                                            pallas_interpret=True)


def test_serve_phase(cfg, capsys):
    out = chip_smoke.serve_phase(cfg, n_requests=3, prompt_lens=(8, 40),
                                 gen_lens=(2, 4), n_slots=4, page_size=16)
    assert out["requests"] == 3
    assert out["logit_rel_err"] <= 1e-4          # f32 model: kernel == jnp
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith("[serve] setup ") and "3/3 requests" in line


def test_aggregate_phase(capsys):
    out = chip_smoke.aggregate_phase(m=5, d=3000, interpret=True)
    assert set(out["rel_err"]) == {"cwmed", "gm", "ctma:cwmed", "ctma:gm"}
    assert "[aggregate m=5 d=3000]" in capsys.readouterr().out


def test_train_phase(cfg, capsys):
    out = chip_smoke.train_phase(cfg, n_layers=2, batch=4, seq=16, steps=2)
    assert len(out["losses"]) == 2
    lines = capsys.readouterr().out.strip().splitlines()
    assert "depth cut 2 -> 2 layers" in lines[0]
    assert "loss finite every step" in lines[-1]


def test_hier_phase_on_four_virtual_devices():
    code = ("import jax, chip_smoke; "
            "chip_smoke.hier_phase(jax.devices(), "
            "leaves={'a': (8, 16, 64), 'b': (8, 32)})")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "split 1/4 over 4 devices" in r.stdout
    assert r.stdout.count("no all-gather") == 2


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_refuses_outside_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60,
                       env=dict(os.environ, PYTHONPATH=""))
    assert r.returncode != 0 and r.stdout == ""


def test_compile_cache_dir(monkeypatch):
    from repro.utils import compile_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert Path(compile_cache_dir()) == ROOT / ".jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache_dir() == "/elsewhere/cache"
