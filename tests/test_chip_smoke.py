"""chip_smoke.py on the CPU: it must refuse to run without a TPU (and
outside a checkout), and its four-chip phase must pass on four emulated
CPU devices at a tiny size (sharding layout, stats placement and the
sharded-vs-dense agreement checks are the same code that runs on the
chip)."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _env(**extra):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_refuses_without_a_tpu():
    proc = subprocess.run([sys.executable, str(SMOKE)], capture_output=True,
                          text=True, env=_env(), timeout=300)
    assert proc.returncode == 2, proc.stderr
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = _env()
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


_FOUR = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, sys.argv[1])
    import jax
    import chip_smoke as cs
    s = cs.Smoke()
    cs.run_four_chips(s, cs.Size(m=4, N=64, d_in=32, L=32,
                                 iters=cs.SHARDED_ITERS),
                      seed=0, devices=jax.devices())
    print("FAILURES:", s.failures)
    sys.exit(1 if s.failures else 0)
    """
)


def test_four_chip_phase_on_emulated_devices():
    proc = subprocess.run([sys.executable, "-c", _FOUR, str(ROOT)],
                          capture_output=True, text=True, env=_env(),
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "sharded ring vs dense" in proc.stdout
    assert "sharded graph_star vs dense" in proc.stdout
