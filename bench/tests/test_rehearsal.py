"""The whole command on the CPU at a tiny size, from a checkout that holds a
cell added by data alone: a new traffic file and a BENCHMARK.json entry.
It runs the generator, the engine and the check, then refuses to print
device metrics. From a directory with only the benchmark in it, it fails."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"


def _checkout(tmp_path, with_program=True):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_program:
        os.symlink(ROOT / "src", tmp_path / "src")
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(DATA / "tiny.json", tmp_path / "bench" / "configs"
                / "tiny.json")
    # a traffic mix no file of the harness names
    shutil.copy(DATA / "tiny-fanout.json", tmp_path / "bench" / "traffic"
                / "tiny-new.json")
    bm["configs"].append({"name": "tiny", "source": "test",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "test"})
    bm["workloads"].append({"name": "tiny.tiny-new", "config": "tiny",
                            "traffic": "tiny-new", "chips": 1,
                            "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp_path


def _run(cwd, *extra):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny.tiny-new",
         "--seed", "2147483700", "--seconds", "2", "--trace", "0", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_new_traffic_file_runs_and_refuses_device_metrics(tmp_path):
    p = _run(_checkout(tmp_path), "--rehearse")
    assert p.returncode == 3, p.stderr[-3000:]
    assert "{" not in p.stdout
    assert "check logit_gap:" in p.stderr
    assert "window open" in p.stderr


def test_benchmark_alone_fails(tmp_path):
    p = _run(_checkout(tmp_path, with_program=False), "--rehearse")
    assert p.returncode not in (0, 3)
    assert "{" not in p.stdout
