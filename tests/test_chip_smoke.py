"""chip_smoke.py off the chip: it must refuse to produce a result, and its
legs must run end to end at rehearsal size on the CPU mesh."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

import horovod_tpu as hvd
from conftest import REPO_ROOT, subprocess_env

SCRIPT = os.path.join(REPO_ROOT, "chip_smoke.py")


def _run(script, **kwargs):
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=120, env=subprocess_env(),
                          **kwargs)


def test_cpu_run_fails_and_names_the_platform():
    """No accelerator -> non-zero exit, the platform it found, no result
    line. A CPU run is never what the script falls back to."""
    proc = _run(SCRIPT)
    assert proc.returncode != 0
    assert "platform: cpu" in proc.stdout
    assert "found platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_alone_without_the_program_fails(tmp_path):
    """In a directory that holds the script and nothing else of the repo
    there is nothing to check: non-zero, no result line."""
    alone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run(alone, cwd=tmp_path)
    assert proc.returncode != 0
    assert "horovod_tpu/" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.slow
def test_rehearsal_legs_on_four_cpu_devices():
    """Legs A-C at rehearsal size through chip_smoke's own functions, on a
    4-device CPU mesh (Pallas in interpret mode)."""
    import chip_smoke  # from the repo root, like horovod_tpu itself

    hvd.shutdown()
    hvd.init(devices=jax.devices()[:4])
    try:
        assert hvd.size() == 4
        chip_smoke.run_legs(chip_smoke.REHEARSAL)
    finally:
        hvd.shutdown()
