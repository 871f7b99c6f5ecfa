"""``bench/run.py`` refuses a machine with no accelerator: it exits non-zero
and prints no result."""

import os
import subprocess
import sys

from bench.lib import loader


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(loader.BENCH, "run.py"), "--workload", "events_edit.device",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=loader.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "accelerator" in proc.stderr
