"""chip_smoke.py refuses to report without a TPU or without the repo."""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu_and_prints_no_result(where, tmp_path):
    """On the CPU (the checkout) and in a directory holding chip_smoke.py
    and nothing else of the repo, the smoke exits non-zero and prints no
    JSON result line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0, r.stdout
    assert not [line for line in r.stdout.splitlines()
                if line.lstrip().startswith("{")], r.stdout
    assert "chip_smoke: FAIL" in r.stderr
