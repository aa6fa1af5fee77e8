"""The op-path reader (``xscope``) and the readers that use it and the
program's host spans, on two small traces recorded on a TPU v5 lite by
``record_trace.py`` (the tiny cell of ``tiny.py``, half a second traced):
``tiny.xplane.pb``, of a program with no named scopes and no host spans of
its own, and ``tiny_scoped.xplane.pb``, of one with both."""

import json
import pathlib
import types

import numpy as np
import pytest

from chipbench import run, xscope, xtrace
from chipbench.tests import tiny

DATA = pathlib.Path(__file__).parent / "data"
TRACES = ["tiny", "tiny_scoped"]
READERS = ["front_adc_ms", "refine_gather_ms", "host_ms", "front_probe_ms",
           "refine_kernel_ms", "rerank_scope_ms"]


def _ctx(name: str):
    path = DATA / f"{name}.xplane.pb"
    tr = xtrace.load(str(path))
    rec = json.loads((DATA / f"{name}.json").read_text())
    lo, hi = tr.window()
    dim = tiny.parts()["config"]["dim"]
    return types.SimpleNamespace(
        trace=tr, lo=lo, hi=hi, xplane=str(path),
        config=tiny.parts()["config"],
        batches={i: b for i, b in enumerate(rec["batches"])},
        queries=lambda qidx: np.zeros((len(qidx), dim)), index=None,
        peak=run.peaks("TPU v5 lite"), notes={}), rec


@pytest.fixture(scope="module")
def plain():
    return _ctx("tiny")


@pytest.fixture(scope="module")
def scoped():
    return _ctx("tiny_scoped")


@pytest.mark.parametrize("name", TRACES)
def test_op_times_equal_the_profiler_readers(name):
    tr = xtrace.load(str(DATA / f"{name}.xplane.pb"))
    planes = xscope.load(str(DATA / f"{name}.xplane.pb"))
    assert sorted(planes) == [d.name for d in tr.devices]
    for dev in tr.devices:
        assert [(o.name, o.start, o.end) for o in planes[dev.name]] == \
            [(o.name, o.start, o.end) for o in dev.ops]


def test_ops_of_the_front_program_carry_its_path(plain):
    """Every op that ran inside an execution of the front program and
    carries an op path carries that program's; the ones without are the
    copies XLA adds for the program's arguments."""
    ctx, _ = plain
    dev = ctx.trace.devices[0]
    ops = xscope.load(ctx.xplane)[dev.name]
    runs = xtrace.module_runs(ctx.trace, "jit__ivf_candidates", ctx.lo,
                              ctx.hi)
    inside = [o for r in runs for o in ops
              if r.start <= o.start and o.end <= r.end]
    assert runs and inside
    for o in inside:
        if o.tf_op.startswith("jit("):
            assert o.tf_op.startswith("jit(_ivf_candidates)"), o.tf_op
        else:
            assert xtrace.op_name(o.name).startswith("copy"), o.name
    assert any(o.tf_op.startswith("jit(_ivf_candidates)") for o in inside)


def test_a_scope_matches_whole_names():
    assert xscope.under("jit(f)/fatrq.front.adc/vmap()/gather:",
                        "fatrq.front.adc")
    assert xscope.under("jit(f)/vmap(fatrq.rerank)/sub:", "fatrq.rerank")
    assert not xscope.under("jit(f)/fatrq.front.adcx/gather:",
                            "fatrq.front.adc")
    assert not xscope.under("jit(f)/fatrq.front/gather:", "fatrq.front.adc")
    assert not xscope.under("", "fatrq.front.adc")


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_scopes_or_spans_reads_nothing(plain, metric):
    ctx, _ = plain
    assert run.load_metric(metric).read(ctx) is None


@pytest.mark.parametrize("metric", READERS)
def test_scoped_readings_are_what_was_recorded(scoped, metric):
    ctx, rec = scoped
    got = run.load_metric(metric).read(ctx)
    assert got is not None and got > 0
    assert got == pytest.approx(rec["metrics"][metric]["value"])


def test_each_scope_lies_inside_its_program(scoped):
    """The probe and the ADC lookup are parts of the front's device time;
    the gathers and the kernel are parts of the refine step's, which holds
    both; the rerank scope is the rerank program's work; the host's time on
    the critical path lies in the device's idle time."""
    ctx, _ = scoped
    read = {m: run.load_metric(m).read(ctx)
            for m in ("front_ms", "front_probe_ms", "front_adc_ms",
                      "refine_ms", "refine_gather_ms", "refine_kernel_ms",
                      "rerank_ms", "rerank_scope_ms", "host_ms",
                      "device_idle")}
    assert 0 < read["front_probe_ms"] + read["front_adc_ms"] \
        <= read["front_ms"]
    roof = run.load_metric("refine_kernel_roofline")
    runs = xtrace.module_runs(ctx.trace, roof.MODULE, ctx.lo, ctx.hi)
    kernel_ms = sum(o.dur for r in runs for o in r.ops
                    if roof.is_kernel(o)) / len(runs) / 1e6
    assert 0 < kernel_ms <= read["refine_kernel_ms"]
    assert read["refine_gather_ms"] + read["refine_kernel_ms"] \
        <= read["refine_ms"]
    assert 0 < read["rerank_scope_ms"] <= read["rerank_ms"]
    idle_ms = read["device_idle"] / 100 * (ctx.hi - ctx.lo) / 1e6
    assert 0 < read["host_ms"] * len(ctx.batches) <= idle_ms


@pytest.mark.parametrize("metric", READERS)
def test_an_empty_window_reads_nothing(scoped, metric):
    ctx, _ = scoped
    empty = types.SimpleNamespace(**{**vars(ctx), "lo": 0, "hi": 1,
                                     "batches": {}})
    assert run.load_metric(metric).read(empty) is None
