"""The entry point refuses to run without the accelerator a cell asks
for: exit code 2 and nothing on standard output."""
import os
import subprocess
import sys

from bench.tests.smoke import REPO


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b.chat",
         "--seed", str(2**40 + 1), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr
