"""``run.py`` refuses to measure without a TPU: it exits non-zero and
prints no result.  It runs in a child process held to the CPU, so this
process never loads the TPU's library."""
import os
import subprocess
import sys

from cpu_cells import BENCH, REPO


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "yelp-bulk", "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
