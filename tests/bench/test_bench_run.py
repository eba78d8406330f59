"""bench/run.py finds no TPU here and exits non-zero with no result, and
fails in a directory that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "hpcg104-cg", "--seed", "4294967311", "--seconds",
        "1", "--trace", "0"]


def _run(cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except ValueError:
            pass
    return False


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    p = _run(ROOT, tmp_path)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert not _has_result(p.stdout)


def test_benchmark_files_alone_do_not_run(tmp_path):
    co = tmp_path / "checkout"
    co.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), co)
    for p in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, p), co / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(co), tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
