import os
import subprocess
import sys

from conftest import ROOT


def test_refuses_a_platform_that_is_not_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2-medium.pretrain-1k", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""
