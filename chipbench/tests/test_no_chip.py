"""Without a TPU the benchmark exits non-zero and prints no result."""
import os
import subprocess
import sys

from chipbench.tests import helpers


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(helpers.BENCH, "run.py"),
         "--workload", "qwen1.5-0.5b.docqa", "--seed", "2147483700",
         "--seconds", "1", "--trace", "0"],
        cwd=helpers.REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
