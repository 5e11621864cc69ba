"""The benchmark measures nothing where there is no TPU or no program."""
import os
import shutil
import subprocess
import sys

from chipbench.harness.spec import BENCH_DIR, ROOT

ARGS = ["--workload", "mag-peak.warm", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _run(ROOT, os.path.join("chipbench", "run.py"))
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_with_only_its_own_files_it_exits_nonzero(tmp_path):
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path, os.path.join("chipbench", "run.py"))
    assert p.returncode != 0
    assert p.stdout == ""
