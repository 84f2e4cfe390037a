"""Without a TPU the benchmark prints no result and exits nonzero: there
is no CPU fallback."""

import os
import subprocess
import sys

from chipbench.bench import ROOT


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "danube3-4b-int4.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
