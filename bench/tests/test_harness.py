"""Whole runs of the harness on the CPU at a tiny size, past its look
for a chip: a sound run is correct, and the control and each fault the
cells can have make ``correct`` false."""
import numpy as np
import pytest

import run
import tiny


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)


@pytest.mark.parametrize("app", ["histo", "hll"])
def test_sound_run_is_correct(app):
    res = run.run_cell(tiny.tiny_cell(app), 2**31 + 3, 2.0, trace=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 50
    assert set(res["metrics"]) >= {"tuples_per_s", "setup_s"}
    assert res["metrics"]["tuples_per_s"]["value"] > 0


@pytest.mark.parametrize("app", ["histo", "hll"])
def test_stale_tail_control_is_not_correct(app):
    res = run.run_cell(tiny.tiny_cell(app), 11, 2.0, trace=False,
                       control="stale_tail")
    assert not res["correct"]
    assert res["checks"]["mismatched_cells"]["value"] > 0


def test_an_altered_answer_is_caught(monkeypatch):
    from repro.serve.session import SessionEngine
    orig = SessionEngine._snapshot

    def altered(self, s):
        out = np.array(orig(self, s))
        out[0, 0] += 1
        return out

    monkeypatch.setattr(SessionEngine, "_snapshot", altered)
    res = run.run_cell(tiny.tiny_cell("histo"), 12, 2.0, trace=False)
    assert not res["correct"]
    assert res["checks"]["mismatched_cells"]["value"] > 0


def test_a_step_that_keeps_its_state_is_caught(monkeypatch):
    from repro.core.executor import ResumableExecutor
    orig = ResumableExecutor.scan_lanes

    def unchanged(self, states, chunks, mask=None):
        _, stats = orig(self, states, chunks, mask)
        return states, stats

    monkeypatch.setattr(ResumableExecutor, "scan_lanes", unchanged)
    res = run.run_cell(tiny.tiny_cell("histo"), 13, 2.0, trace=False)
    assert not res["correct"]
    assert res["checks"]["mismatched_cells"]["value"] > 0


def test_int16_control_is_not_correct():
    # a hot tenant whose top key passes 2**15 within the run
    cell = tiny.tiny_cell("histo")
    cell.traffic.update(key_alphas=[3.0], append_tuples=[2048, 4096])
    res = run.run_cell(cell, 2**31 + 17, 2.0, trace=False, control="int16")
    assert not res["correct"]
    assert res["checks"]["mismatched_cells"]["value"] > 0
    sound = run.run_cell(cell, 2**31 + 17, 2.0, trace=False)
    assert sound["correct"], sound["checks"]
