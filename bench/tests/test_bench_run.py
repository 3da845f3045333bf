"""bench/run.py refuses to run without an accelerator."""
import os
import subprocess
import sys

from bench import manifest


def test_exits_nonzero_with_no_result_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(manifest.BENCH_DIR / "run.py"), "--workload",
         "qwen3-4b.chat-mt-sat", "--seed", str(2**40 + 1), "--seconds", "10",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=manifest.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "accelerator" in p.stderr
