"""`chip_smoke.py` refuses to run anywhere but on a TPU."""
import os
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_exits_nonzero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, "chip_smoke.py"],
                         cwd=str(REPO_ROOT), env=env, capture_output=True,
                         text=True, timeout=300)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    assert '"ok"' not in res.stdout
