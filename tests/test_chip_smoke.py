"""``chip_smoke.py``: it refuses to run without a TPU, and its phases pass
at smoke size on the CPU (the chip runs them at published widths)."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _run(cwd, extra_env=None, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, str(Path(cwd) / "chip_smoke.py")], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def _no_ok(proc):
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "chip_smoke: FAIL" in proc.stderr


def test_refuses_without_a_tpu():
    proc = _run(ROOT)
    _no_ok(proc)
    assert "no TPU" in proc.stderr


def test_refuses_outside_the_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    _no_ok(_run(tmp_path))


@pytest.fixture
def smoke_module():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke

        yield chip_smoke
    finally:
        sys.path.remove(str(ROOT))


def test_one_chip_phases_at_smoke_size(smoke_module, capsys):
    smoke_module.run_one_chip(smoke_module.Clock(), smoke=True)
    out = capsys.readouterr().out
    assert "reference check: 60 queries" in out
    assert "tier1: demotions=" in out


def test_four_chip_path_at_smoke_size():
    """The sharded-L2 path on 4 virtual CPU devices (own process: the
    device count is fixed when JAX starts)."""
    code = (
        "import sys; sys.path.insert(0, '.'); import chip_smoke as cs; "
        "cs.run_four_chips(cs.Clock(), smoke=True)"
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "L2 shards on 4 devices" in proc.stdout
