"""A whole run of a cell at a CPU test's size: set-up, window, reference
and result line, with only the harness's look for a chip skipped."""

import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import data, run
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def served():
    """A run, and the engine it drove (kept past the run's end)."""
    kept = []

    def keep(engine):
        kept.append(engine)
        return engine

    res = run.run_cell(tiny.parts(), tiny.SEED, 1.0, False,
                       require_tpu=False, t_start=time.perf_counter(),
                       engine_hook=keep)
    return res, kept[0]


@pytest.fixture(scope="module")
def result(served):
    return served[0]


def test_a_sound_run_is_correct(result):
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert result["checks"]["dist_gap"]["value"] <= 1e-6
    assert result["info"]["window_compiles"] == 0


def test_window_is_whole_calls_of_all_callers(result):
    win = result["window"]
    assert len(win.sends) >= 2                      # at least the recall set
    assert all(n == 16 for n in win.sizes)
    assert result["attempted"] == 16 * len(win.sends)
    # the recall set is the same questions in every run, in the seed's order
    rs = tiny.parts()["mix"].recall_set
    assert sorted(win.qidx[:rs]) == list(range(rs))
    assert len(set(win.qidx)) == result["attempted"]
    assert win.returns[-1] - win.sends[0] >= 1.0
    # every micro-batch holds 8 requests and carries the front's counter
    assert all(len(b["qidx"]) == 8 and b["front_cand"] > 0
               for b in win.batches.values())


def test_result_line_holds_the_end_to_end_metrics(result, capsys):
    rec = dict(result)
    run.report(rec)
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    m = line["metrics"]
    assert {"qps", "p95_ms", "recall_at_10", "setup_s"} <= set(m)
    assert m["qps"]["unit"] == "queries/s" and m["qps"]["value"] > 0
    assert 0.8 <= m["recall_at_10"]["value"] <= 1.0
    assert line["device"]["platform"] == "cpu"
    assert out.err.strip().splitlines()[-1].startswith("check unanswered")


def test_off_the_chip_it_exits_non_zero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(run.BENCH / "run.py"),
                        "--workload", tiny.CELL, "--seed", str(tiny.SEED),
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=run.ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_probed_candidates_match_the_front_counter(served):
    """The roofline counts each micro-batch's candidates from the index's
    centroids and list lengths; the program's own counter agrees."""
    res, engine = served
    roof = run.load_metric("refine_kernel_roofline")
    index = run.probe_index(engine, tiny.parts()["config"])
    spec = data.Spec(tiny.parts()["config"])
    batches = list(res["window"].batches.values())
    got = [roof.probed_candidates(index, data.queries(spec, b["qidx"]))
           for b in batches]
    assert got == [b["front_cand"] for b in batches]
