"""The whole command on the CPU at a tiny size, from a checkout that holds a
cell added by data alone: a new traffic file and a BENCHMARK.json entry.
It runs the generator, the engine and the check, then refuses to print
device metrics. From a directory with only the benchmark in it, it fails.
A configuration of a model family the harness has never seen, a mixture of
experts, runs the same way with its family file added and no file of the
harness changed; and every counter of the engine's stats reaches the run."""
import json
import os
import re
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


def _run(cwd, *extra, workload="tiny.tiny-new"):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
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


def _files(bench):
    return {p.relative_to(bench): p.read_bytes() for p in bench.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_model_family_runs_by_files_alone(tmp_path):
    """A tiny MoE on the program's ``repro/models/moe.py``: its family file
    (weights, float32 reference, operation counts), its configuration, a
    traffic file and a BENCHMARK.json entry are all that is added."""
    assert not (ROOT / "bench" / "families" / "toy_moe.py").exists()
    co = _checkout(tmp_path)
    added = {Path("families/toy_moe.py"): DATA / "toy_moe.py",
             Path("configs/toy-moe.json"): DATA / "toy-moe.json",
             Path("traffic/toy-moe-fanout.json"): DATA / "tiny-fanout.json"}
    for dst, src in added.items():
        shutil.copy(src, co / "bench" / dst)
    bm = json.loads((co / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "toy-moe", "source": "test",
                          "file": "bench/configs/toy-moe.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "toy-moe.toy-moe-fanout",
                            "config": "toy-moe", "traffic": "toy-moe-fanout",
                            "chips": 1, "why": "test"})
    (co / "BENCHMARK.json").write_text(json.dumps(bm))
    harness, checkout = _files(ROOT / "bench"), _files(co / "bench")
    extra = {Path("configs/tiny.json"), Path("traffic/tiny-new.json")}
    assert set(checkout) == set(harness) | set(added) | extra
    assert all(checkout[f] == harness[f] for f in harness)

    p = _run(co, "--rehearse", workload="toy-moe.toy-moe-fanout")
    assert p.returncode == 3, p.stderr[-3000:]
    assert "{" not in p.stdout
    gap, limit = re.search(r"check logit_gap: (\S+) limit (\S+)",
                           p.stderr).groups()
    assert float(gap) <= float(limit), p.stderr[-3000:]


def test_every_engine_counter_reaches_the_run(monkeypatch):
    """A counter the engine's ``stats()`` gains reaches ``run.stats_open``
    and ``run.stats_close`` with no change to the harness, beside the five
    short names the readers have always used."""
    import time

    from repro.serving.engine import EngineStats

    from bench import harness
    from bench.spec import reader
    calls = []
    stats = EngineStats.__call__

    def with_probe(self):
        calls.append(1)
        return {**stats(self), "probe_counter": len(calls)}
    monkeypatch.setattr(EngineStats, "__call__", with_probe)
    conf = harness.bmodel.load(DATA / "tiny.json")
    traffic = harness.bmodel.load(DATA / "tiny-fanout.json")
    t0 = time.perf_counter()
    dep = harness.Deployment(conf, traffic, 2**31 + 79, t0,
                             shape_warmup=False)
    run = dep.serve(traffic, 1.0, False, t0)
    dep.close()
    a, b = run.stats_open, run.stats_close
    assert 0 < a["probe_counter"] < b["probe_counter"]
    for name in ("computed", "reused", "decode_tokens", "decode_steps",
                 "duplicated", "decode_table_pages", "decode_live_pages",
                 "pages_active"):
        assert name in a and name in b
    assert a["computed"] == a["prefill_tokens_computed"]
    assert b["decode_steps"] > a["decode_steps"]
    share = reader("kv.decode_live_page_share")(run)
    assert 0 < share <= 100
