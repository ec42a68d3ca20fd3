"""The comparison that decides ``correct``, driven through a whole run at a
CPU size with the chip check skipped: a sound run passes; the float8
control, a token altered where it is produced, and a decode step that
leaves the KV pool unchanged all fail."""
import os
from pathlib import Path

import pytest

from bench import harness
from bench.run import metrics_of, result
from bench.spec import Cell

DATA = Path(__file__).parent / "data"
CELL = Cell("tiny.tiny-fanout", 1, DATA / "tiny.json",
            DATA / "tiny-fanout.json", [], [])
LIMIT = harness.bmodel.load(DATA / "tiny.json")["check"]["max_logit_gap"]


def _run(fault=None, control=False):
    import time
    return harness.run_cell(CELL, 2**31 + 77, 2.0, False, time.perf_counter(),
                            fault=fault, control=control)


def _correct(run, checks):
    import jax
    return result(run, checks, CELL, False, jax.devices())["correct"]


def test_sound_run_passes_and_control_fails():
    run, checks = _run(control=True)
    assert checks["sampled_tokens"] >= checks["sampled_tokens_min"]
    assert checks["logit_gap"] <= LIMIT
    assert checks["control_gap"] > LIMIT
    assert _correct(run, checks)
    assert not _correct(run, harness.as_control(checks))
    assert all(r.finished for r in run.window)
    counted = metrics_of(run, [{"name": "engine.setup_programs",
                                "unit": "count"}])
    assert counted["engine.setup_programs"]["value"] > 0


def _altered_token(engine):
    plane_cls = type(engine.decode_plane)
    step = plane_cls.step

    def altered(self, seqs):
        out = step(self, seqs)
        out[0] = (out[0] + 1) % engine.cfg.vocab_size
        return out
    return plane_cls, altered


def _kv_left_unchanged(engine):
    from repro.serving.decode import StackedDecoders as group_cls
    step = group_cls.step

    def unchanged(self, seqs):
        pool = self.kvpool
        keep = pool.absorb_decode_state
        pool.absorb_decode_state = lambda state: None
        try:
            return step(self, seqs)
        finally:
            pool.absorb_decode_state = keep
    return group_cls, unchanged


@pytest.mark.parametrize("fault", [_altered_token, _kv_left_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_faults_fail(fault, monkeypatch):
    def plant(engine):
        cls, fn = fault(engine)
        monkeypatch.setattr(cls, "step", fn)
    run, checks = _run(fault=plant)
    assert checks["logit_gap"] > LIMIT, checks
    assert not _correct(run, checks)


def test_cli_refuses_without_a_chip():
    import subprocess
    import sys
    root = Path(__file__).resolve().parents[2]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-8b-l4-x2.agent-longctx-grid", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
