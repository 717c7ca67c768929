"""The shipped examples must actually run (reference DeepSpeedExamples role)."""
import os
import subprocess
import sys

import pytest

@pytest.mark.parametrize("cmd", [
    ["examples/train.py", "--model", "tiny", "--seq_len", "32", "--steps", "3"],
    ["examples/generate.py", "--model", "tiny", "--batch", "2",
     "--prompt_len", "16", "--new_tokens", "4"],
    ["examples/rlhf.py", "--model", "tiny", "--iters", "1",
     "--new_tokens", "4"],
    ["examples/stable_diffusion.py", "--steps", "3", "--size", "8"],
], ids=["train", "generate", "rlhf", "stable_diffusion"])
@pytest.mark.slow
def test_example_runs(cmd, tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # JAX_PLATFORMS=cpu comes from conftest; the examples place a compile
    # cache, and a test places it outside the checkout
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    proc = subprocess.run(
        [sys.executable] + cmd, capture_output=True, text=True,
        timeout=900, cwd=repo, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
