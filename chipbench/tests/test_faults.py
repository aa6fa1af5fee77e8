"""``correct`` comes out false when the timed path is broken underneath:
the control (the reference in bfloat16) in the system's place, half of each
micro-batch left out, and an answer altered where it is produced."""

import time

import jax.numpy as jnp
import pytest

from chipbench import control, run
from chipbench.tests import tiny


def _run(**kw):
    return run.run_cell(tiny.parts(), tiny.SEED, 0.5, False,
                        require_tpu=False, t_start=time.perf_counter(), **kw)


def test_the_control_is_not_correct():
    res = _run(system=control.system)
    assert not res["correct"]
    assert res["checks"]["dist_gap"]["value"] > \
        res["checks"]["dist_gap"]["limit"]


def test_half_of_each_micro_batch_left_out(monkeypatch):
    from repro.serving import scheduler
    pad = scheduler.pad_chunk

    def half(chunk, bucket):
        q, valid = pad(chunk, bucket)
        h = q.shape[0] // 2
        return jnp.concatenate([q[:h], q[:q.shape[0] - h]]), valid

    monkeypatch.setattr(scheduler, "pad_chunk", half)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["dist_gap"]["value"] > 0.01


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    from repro.anns import stages
    rerank = stages._rerank_survivors

    def altered(x, *a, **kw):
        ids, d, n = rerank(x, *a, **kw)
        return ids.at[:, 0].set((ids[:, 0] + 1) % x.shape[0]), d, n

    monkeypatch.setattr(stages, "_rerank_survivors", altered)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["dist_gap"]["value"] > 1e-3


@pytest.mark.parametrize("n_left_out", [1, 16])
def test_answers_that_never_come(n_left_out):
    def drop(engine):
        serve = engine.serve

        def lossy(queries):
            return serve(queries)[:-n_left_out]
        engine.serve = lossy
        return engine

    res = _run(engine_hook=drop)
    assert not res["correct"]
    assert res["failed"] >= n_left_out
    assert res["checks"]["unanswered"]["value"] == res["failed"]
