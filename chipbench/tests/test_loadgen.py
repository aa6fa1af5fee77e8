"""Traffic is a function of the seed, and the window's arithmetic is over
whole calls and every request."""

import json

import numpy as np
import pytest

from chipbench import loadgen, run

SEED = 2**31 + 4242


def mix(**kw):
    return loadgen.Mix(**{"callers": 64, "max_batch": 32, "backend": "pallas",
                          "warmup_calls": 1, "recall_set": 128,
                          "check_sample": 256, **kw})


def test_closed64_mix_file_loads():
    m = loadgen.Mix.load(run.BENCH / "traffic" / "closed64.json")
    assert (m.callers, m.max_batch, m.backend) == (64, 32, "pallas")
    assert m.recall_set == 256 and m.check_sample == 384


@pytest.mark.parametrize("fields", [
    {"callers": 64},                                  # fields left out
    {**vars(mix()), "arrival": "poisson"},            # a field it lacks
])
def test_a_mix_must_name_exactly_its_fields(tmp_path, fields):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"why": "test", **fields}))
    with pytest.raises(ValueError):
        loadgen.Mix.load(p)


def test_query_indices_follow_the_seed():
    m = mix()
    a = loadgen.query_indices(m, SEED, 500)
    assert np.array_equal(a, loadgen.query_indices(m, SEED, 500))
    assert np.array_equal(a[:100], loadgen.query_indices(m, SEED, 100))
    b = loadgen.query_indices(m, SEED + 1, 500)
    assert not np.array_equal(a, b)
    # every seed asks the same questions, block by block, in its order
    assert len(set(a.tolist())) == 500
    for k in range(0, 384, 128):
        assert sorted(a[k:k + 128]) == list(range(k, k + 128))
        assert sorted(b[k:k + 128]) == list(range(k, k + 128))


def test_queries_are_made_from_the_seed_in_blocks():
    calls = []

    def make(idx):
        calls.append(idx[0])
        return np.stack([np.full(3, i, np.float32) for i in idx])

    q = loadgen.Queries(make)
    out = q.get([5, 70, 6])
    assert out[:, 0].tolist() == [5, 70, 6]
    q.get([1, 2])
    assert calls == [0, 64]                # one block each, made once


def _window(call_walls, sizes):
    w = loadgen.Window()
    t = 100.0
    for dur, n in zip(call_walls, sizes):
        w.sends.append(t)
        w.returns.append(t + dur)
        w.sent += [t] * n
        w.done += [t + dur] * n
        w.answers += [(np.arange(10), np.arange(10.0))] * n
        t += dur + 0.5                      # the caller's own time
    return w


def test_qps_counts_whole_calls_over_their_wall_time():
    w = _window([2.0, 3.0, 4.0], [64, 64, 64])
    # first send at 100, last return at 100 + 2 + .5 + 3 + .5 + 4
    assert w.qps() == pytest.approx(192 / 10.0)
    w.answers[5] = None                     # an unanswered request
    assert w.failed == 1
    assert w.qps() == pytest.approx(191 / 10.0)


def test_p95_is_over_every_request_not_over_call_medians():
    w = _window([1.0] * 19 + [9.0], [10] * 19 + [30])
    lat = w.latencies_ms()
    assert len(lat) == 220
    # 30 of 220 requests took 9 s: the 95th percentile is among them,
    # while the 95th percentile of the 20 calls' medians would be 1 s
    assert loadgen.percentile(lat, 95) == pytest.approx(9000.0)
    assert np.percentile([1000.0] * 19 + [9000.0], 95) < 9000.0 * 0.6


def test_check_positions_hold_the_recall_set_and_follow_the_seed():
    m = mix(recall_set=128, check_sample=256)
    w = _window([1.0] * 8, [64] * 8)
    a = loadgen.check_positions(m, SEED, w)
    assert a[:128].tolist() == list(range(128)) and len(a) == 256
    assert len(set(a.tolist())) == 256 and a.max() < 512
    assert np.array_equal(a, loadgen.check_positions(m, SEED, w))
    assert not np.array_equal(a, loadgen.check_positions(m, SEED + 1, w))
