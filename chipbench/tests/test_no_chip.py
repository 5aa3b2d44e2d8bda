"""Without a TPU the harness prints no result and exits non-zero."""
import os
import subprocess
import sys

from chipbench import bench


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_on_cpu():
    p = _run(["--workload", "qwen3-14b.train-4x512", "--seed", "2147483659",
              "--seconds", "1", "--trace", "0"], bench.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no system under test: no result, a non-zero exit."""
    import shutil
    shutil.copy(bench.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(["--workload", "qwen3-14b.train-4x512", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
