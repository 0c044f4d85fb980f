"""CPU rehearsal of ``chip_smoke.py``.

Its phases run here at the ``qwen3_1_7b`` smoke config, the Pallas kernels
in interpret mode and paged attention forced onto the fused kernel; the
four-chip phase runs in a child process on four virtual CPU devices.  The
script itself must refuse to run without a TPU, and without the rest of
the repository, printing no result line.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

SMOKE = ["--arch", "qwen3_1_7b", "--smoke"]
SERVE = ["--slots", "2", "--prompt-len", "16", "--gen", "4",
         "--requests", "3"]
TRAIN = ["--steps", "2", "--seq-len", "32", "--global-batch", "2"]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(monkeypatch):
    from repro.kernels import paged_attn
    monkeypatch.setattr(paged_attn, "FORCE_FUSED", True)
    return _load_smoke()


def test_serve_phases_pass_at_smoke_size(smoke, capsys):
    fails = smoke.serve_phases(SMOKE, SERVE, smoke.CompileClock())
    out = capsys.readouterr().out
    assert fails == []
    for label in ("acdc paged", "acdc fp32 paged", "acdc fp32 contiguous",
                  "dense paged"):
        assert f"[phase] {{\"phase\": \"serve {label}\"" in out
    assert "[route] acdc paged: PAGED_ATTN_DISPATCHES {'fused': 1, " \
           "'gather': 0}" in out
    assert "12/12 tokens identical before the streams part" in out


def test_train_phase_passes_at_smoke_size(smoke, capsys):
    assert smoke.train_phase(SMOKE, TRAIN, smoke.CompileClock()) == []
    assert "[train] acdc: losses [" in capsys.readouterr().out


def test_checks_catch_bad_requests(smoke):
    from repro.serving.request import Request
    good = Request(rid=0, prompt=[1, 2], generated=[3, 4],
                   finish_reason="length")
    cut = Request(rid=1, prompt=[1], generated=[3],
                  finish_reason="cache_full")
    wild = Request(rid=2, prompt=[1], generated=[9], finish_reason="eos")
    assert smoke.check_finished([good], 8, "x") == []
    assert len(smoke.check_finished([good, cut, wild], 8, "x")) == 2


def test_four_chip_phase_on_virtual_devices():
    """The (data=2, model=2) launcher mesh, Pallas kernels under
    ``shard_map``, against one device: four virtual CPU devices need a
    process of their own."""
    code = ("import sys, importlib.util\n"
            f"spec = importlib.util.spec_from_file_location('s', {str(SCRIPT)!r})\n"
            "s = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(s)\n"
            f"fails = s.four_chip_phase({SMOKE!r}, {TRAIN!r}, s.CompileClock())\n"
            "print('FAILS', fails)\n"
            "sys.exit(1 if fails else 0)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "[mesh] mesh data=2 model=2: {'data': 2, 'model': 2} over 4 " \
           "device(s)" in proc.stdout
    assert "[grads] mesh vs one device, 2 live blocks, batch 1" in proc.stdout
    assert "FAILS []" in proc.stdout


@pytest.mark.parametrize("alone", [False, True], ids=["cpu", "script-alone"])
def test_script_refuses_without_tpu(tmp_path, alone):
    script = SCRIPT
    if alone:
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          cwd=script.parent, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
