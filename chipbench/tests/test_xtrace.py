"""The reduction from a device trace to the per-layer metrics, on a small
trace recorded on a TPU v5 lite (``record_trace.py``: the tiny cell of
``tiny.py``, half a second traced), and the kernel's lower-bound work."""

import json
import pathlib
import types

import numpy as np
import pytest

from chipbench import run, xtrace
from chipbench.tests import tiny

DATA = pathlib.Path(__file__).parent / "data"
PER_LAYER = ["device_idle", "front_ms", "refine_ms", "refine_kernel_roofline",
             "rerank_ms"]


@pytest.fixture(scope="module")
def recorded():
    tr = xtrace.load(str(DATA / "tiny.xplane.pb"))
    rec = json.loads((DATA / "tiny.json").read_text())
    lo, hi = tr.window()
    # An index of one list of one row, probed by each of as many queries as
    # the micro-batch had candidates: the probe count reproduces the
    # recorded candidates of every micro-batch.
    dim = tiny.parts()["config"]["dim"]
    ctx = types.SimpleNamespace(
        trace=tr, lo=lo, hi=hi, config=tiny.parts()["config"],
        batches={i: {"qidx": list(range(b["front_cand"])),
                     "front_cand": b["front_cand"]}
                 for i, b in enumerate(rec["batches"])},
        queries=lambda qidx: np.zeros((len(qidx), dim)),
        index={"centroids": np.zeros((1, dim)), "list_len": np.ones(1, int),
               "nprobe": 1},
        peak=run.peaks("TPU v5 lite"), notes={})
    return tr, rec, ctx


def test_union_merges_and_clips():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36), (50, 60)]
    assert xtrace.union(iv, 2, 55) == [(2, 20), (30, 40), (50, 55)]
    assert xtrace.union([(5, 5), (9, 3)], 0, 10) == []


def test_names():
    assert xtrace.module_name("jit__ivf_candidates(1378)") == \
        "jit__ivf_candidates"
    assert xtrace.op_name("%fusion.2 = f32[1440]{0:T(1024)} fusion(f32[3]"
                          "{0} %a)") == "fusion.2 f32[1440]"
    assert xtrace.op_name("%k.1 = (f32[32,92]{2,1:T(8,128)}, s32[8]{0}) "
                          "custom-call(u8[2]") == "k.1 (f32[32,92], s32[8])"


def test_recorded_trace_has_one_chip_and_the_window(recorded):
    tr, rec, ctx = recorded
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    assert (ctx.hi - ctx.lo) / 1e9 == pytest.approx(
        rec["device"]["window_s"])
    busy = xtrace.busy_ns(tr.devices[0], ctx.lo, ctx.hi)
    assert 0 < busy < ctx.hi - ctx.lo
    assert busy / 1e9 == pytest.approx(rec["device"]["busy_s"])


def test_every_micro_batch_ran_each_stage_once(recorded):
    tr, rec, ctx = recorded
    for metric in ("front_ms", "refine_ms", "rerank_ms"):
        module = run.load_metric(metric).MODULE
        runs = xtrace.module_runs(tr, module, ctx.lo, ctx.hi)
        assert len(runs) == len(rec["batches"]), module
        assert all(0 < r.busy <= r.end - r.start for r in runs)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_reduction_reads_what_was_recorded(recorded, metric):
    _, rec, ctx = recorded
    got = run.load_metric(metric).read(ctx)
    assert got is not None and got > 0
    if metric in rec["metrics"]:
        assert got == pytest.approx(rec["metrics"][metric]["value"])
    if metric.endswith("roofline") or metric == "device_idle":
        assert got <= 100.0


def test_a_reader_that_finds_nothing_returns_nothing(recorded):
    tr, _, ctx = recorded
    empty = types.SimpleNamespace(**{**vars(ctx), "lo": 0, "hi": 1,
                                     "batches": {}})
    for metric in PER_LAYER[1:]:
        assert run.load_metric(metric).read(empty) is None, metric


def test_roofline_counts_the_recorded_candidates(recorded):
    tr, rec, ctx = recorded
    roof = run.load_metric("refine_kernel_roofline")
    got = roof.read(ctx)
    runs = xtrace.module_runs(tr, roof.MODULE, ctx.lo, ctx.hi)
    kernel_s = sum(o.dur for r in runs for o in r.ops if roof.is_kernel(o))
    least = sum(roof.bound(ctx.config["dim"], b["front_cand"], ctx.peak)[0]
                for b in rec["batches"])
    assert got == pytest.approx(100.0 * least / (kernel_s / 1e9))
    assert ctx.notes["refine_kernel_candidates"] == \
        ctx.notes["refine_kernel_candidates_front_counter"]


def test_probed_candidates_sum_the_nearest_lists():
    roof = run.load_metric("refine_kernel_roofline")
    index = {"centroids": np.array([[0.0, 0.0], [1.0, 0.0], [0.0, 3.0],
                                    [5.0, 5.0]]),
             "list_len": np.array([10, 20, 40, 80]), "nprobe": 2}
    q = np.array([[0.1, 0.0], [0.0, 2.9], [4.0, 4.0]])
    # nearest two lists: {0, 1}, {2, 0}, {3, 2}
    assert roof.probed_candidates(index, q) == 30 + 50 + 120


def test_roofline_needs_one_kernel_per_micro_batch(recorded):
    _, _, ctx = recorded
    fewer = types.SimpleNamespace(**{**vars(ctx), "batches": dict(
        list(ctx.batches.items())[:-1])})
    assert run.load_metric("refine_kernel_roofline").read(fewer) is None


def test_breakdown_lists_at_most_ten(recorded):
    tr, _, ctx = recorded
    ops = xtrace.top_ops(tr, ctx.lo, ctx.hi)
    gaps = xtrace.idle_gaps(tr, ctx.lo, ctx.hi)
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert ops[0][0].startswith("jit_")
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))


PEAK = {"ops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("dim,g", [(768, 154), (1536, 308), (64, 13)])
def test_kernel_lower_bound_counts_level0_records(dim, g):
    roof = run.load_metric("refine_kernel_roofline")
    t, which = roof.bound(dim, 1000, PEAK)
    assert which == "hbm"
    assert t == pytest.approx(1000 * (g + 16) / 819e9)


def test_kernel_lower_bound_turns_to_ops_when_compute_dominates():
    roof = run.load_metric("refine_kernel_roofline")
    t, which = roof.bound(768, 10, {"ops_per_s": 1e9,
                                    "hbm_bytes_per_s": 819e9})
    assert which == "ops" and t == pytest.approx(10 * 768 / 1e9)
