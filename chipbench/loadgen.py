"""The traffic generator: drives ``ServingEngine.serve`` from a mix file.

A mix (``traffic/<name>.json``) is data only, and every field is required:

* ``callers`` — callers in a closed loop: each waits for its answer before
  it sends the next request, so every call hands the engine the
  ``callers`` ready requests.  Every request asks another question of the
  configuration's pool, each block of ``recall_set`` questions in an order
  drawn from the seed.
* ``max_batch``, ``backend``: how the engine is built (no result cache).
* ``warmup_calls``: calls of ``callers`` warm-up queries before the window.
* ``recall_set``: the first requests of the window, answered in every run,
  over which recall is reported; ``check_sample``: how many answered
  requests ``correct`` judges (the recall set, then a draw from the seed).

A request's latency runs from the send of its call to the call's return.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import time
from dataclasses import dataclass, field

import numpy as np

GEN_BLOCK = 64


@dataclass(frozen=True)
class Mix:
    callers: int
    max_batch: int
    backend: str
    warmup_calls: int
    recall_set: int
    check_sample: int

    @classmethod
    def load(cls, path: pathlib.Path) -> "Mix":
        raw = json.loads(pathlib.Path(path).read_text())
        raw.pop("why", None)
        names = {f.name for f in dataclasses.fields(cls)}
        if set(raw) != names:
            raise ValueError(f"{path}: a mix has exactly the fields "
                             f"{sorted(names)} and 'why'; got {sorted(raw)}")
        return cls(**raw)


@dataclass
class Window:
    """What the measured window did, call by call and request by request."""

    sends: list = field(default_factory=list)     # per call: wall send time
    returns: list = field(default_factory=list)   # per call: wall return time
    sizes: list = field(default_factory=list)     # per call: requests sent
    sent: list = field(default_factory=list)      # per request: send time
    done: list = field(default_factory=list)      # per request: answer time
    qidx: list = field(default_factory=list)      # per request: query index
    answers: list = field(default_factory=list)   # per request: (ids, dists)
    batches: dict = field(default_factory=dict)   # batch id → its requests

    @property
    def attempted(self) -> int:
        return len(self.sent)

    @property
    def failed(self) -> int:
        return sum(a is None for a in self.answers)

    def latencies_ms(self) -> np.ndarray:
        return np.asarray([(d - s) * 1e3 for d, s in zip(self.done, self.sent)
                           if d is not None])

    def qps(self) -> float:
        """Requests answered over the wall time of whole calls."""
        span = self.returns[-1] - self.sends[0]
        return (self.attempted - self.failed) / span


def percentile(values, q: float) -> float | None:
    """q-th percentile (0-100) with linear interpolation, over all values;
    None when there are none."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


class Queries:
    """Query vectors by index, made on the device in fixed blocks of
    GEN_BLOCK and kept on the host."""

    def __init__(self, make):
        self._make = make            # (idx array of GEN_BLOCK) → (B, D)
        self._blocks: dict[int, np.ndarray] = {}

    def get(self, idx) -> np.ndarray:
        rows = []
        for i in idx:
            b = int(i) // GEN_BLOCK
            if b not in self._blocks:
                self._blocks[b] = np.asarray(self._make(
                    np.arange(b * GEN_BLOCK, (b + 1) * GEN_BLOCK)))
            rows.append(self._blocks[b][int(i) % GEN_BLOCK])
        return np.stack(rows)


def query_indices(mix: Mix, seed: int, n: int) -> np.ndarray:
    """Pool index of the question each of the first n requests asks."""
    return Order(mix, seed).take(0, n)


class Order:
    """``query_indices`` of a growing window, each block drawn once: the
    window's own work between calls stays the same however long it runs."""

    def __init__(self, mix: Mix, seed: int):
        self._b, self._seed = mix.recall_set, seed
        self._blocks: list[np.ndarray] = []

    def take(self, j: int, n: int) -> np.ndarray:
        """Indices of requests j .. j+n-1."""
        b = self._b
        while len(self._blocks) * b < j + n:
            k = len(self._blocks)
            rng = np.random.default_rng([self._seed, 4, k])
            self._blocks.append(k * b + rng.permutation(b))
        got = self._blocks[j // b:-(-(j + n) // b)]
        return (np.concatenate(got)[j % b:j % b + n] if got
                else np.zeros(0, int))


def _record(win: Window, responses: list, qidx, t_send: float,
            t_ret: float) -> None:
    by_pos = {pos: r for pos, r in responses}
    for pos, qi in enumerate(qidx):
        r = by_pos.get(pos)
        win.sent.append(t_send)
        win.qidx.append(int(qi))
        win.done.append(t_ret if r is not None else None)
        win.answers.append((np.asarray(r.ids), np.asarray(r.distances))
                           if r is not None else None)
        if r is not None and r.batch is not None:
            b = win.batches.setdefault(r.batch, {"qidx": []})
            b["qidx"].append(int(qi))
            acc = r.cost.ledger.get("coarse:hbm") if r.cost else None
            b["front_cand"] = acc.accesses if acc is not None else None


def _serve(engine, queries: np.ndarray, call_no: int, annotate) -> list:
    """One ``serve`` call → [(position in the call, response)].  The engine
    numbers requests in arrival order from the count it has admitted."""
    base = engine.stats.requests
    with annotate("chipbench.serve", call=call_no, n=len(queries)):
        resp = engine.serve(queries)
    return [(r.rid - base, r) for r in resp]


def warm_up(engine, mix: Mix, warm_queries, annotate) -> None:
    for c in range(mix.warmup_calls):
        q = warm_queries(np.arange(c * mix.callers, (c + 1) * mix.callers))
        _serve(engine, np.asarray(q), -1 - c, annotate)


def run_window(engine, mix: Mix, seed: int, seconds: float, queries: Queries,
               annotate) -> Window:
    """Drive the engine for ``seconds`` of wall time: until the call during
    which they elapse returns, and at least through the recall set."""
    win = Window()
    order = Order(mix, seed)
    min_calls = math.ceil(mix.recall_set / mix.callers)
    while True:
        qi = order.take(win.attempted, mix.callers)
        with annotate("chipbench.queries", n=mix.callers):
            q = queries.get(qi)
        t_send = time.perf_counter()
        resp = _serve(engine, q, len(win.sends), annotate)
        t_ret = time.perf_counter()
        _record(win, resp, qi, t_send, t_ret)
        win.sends.append(t_send)
        win.returns.append(t_ret)
        win.sizes.append(mix.callers)
        if t_ret - win.sends[0] >= seconds and len(win.sends) >= min_calls:
            return win


def check_positions(mix: Mix, seed: int, win: Window) -> np.ndarray:
    """Request positions ``correct`` judges: the recall set, then a draw
    from the seed among the other requests of the window."""
    head = list(range(min(mix.recall_set, win.attempted)))
    rest = np.arange(len(head), win.attempted)
    extra = max(0, min(mix.check_sample - len(head), len(rest)))
    rng = np.random.default_rng([seed, 3])
    pick = np.sort(rng.choice(rest, size=extra, replace=False)) \
        if extra else np.zeros(0, int)
    return np.asarray(head + pick.tolist(), int)
