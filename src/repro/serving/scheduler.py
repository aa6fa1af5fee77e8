"""Continuous-batching serving engine over ``anns.api.Database``.

The query layer (PR 5-7) answers *one batch at a time*: callers hand
``db.query`` a query stack and block until the staged executor finishes.
A serving frontend sees a different shape of work — an open-loop stream
of single-query requests with deadlines and tenants — and pays for the
mismatch twice: per-request dispatch recompiles nothing but still runs
the datapath at batch size 1 (device utilization ∝ batch size), and a
hot tenant can starve everyone else out of the refine budget.

``ServingEngine`` closes the gap with four cooperating pieces:

* **Admission scheduler** — requests enter a deadline-ordered (EDF)
  admission queue under a deterministic virtual clock (microseconds).
  The engine is a discrete-event simulator over that clock: identical
  (seed, arrival trace) inputs produce identical batch boundaries,
  which is what makes the scheduler testable at all.
* **Coalescer** — admitted requests group by service class
  ``(k, degraded)``; a class's micro-batch closes when it reaches
  ``max_batch`` or its oldest member has waited ``max_wait_us``.
  Batches pad to the compiled power-of-two buckets
  (``executor.bucket_for`` / ``pad_chunk``), so the plan-keyed executor
  cache is reused across every batch size — the engine never triggers
  a recompile at dispatch time.
* **Double-buffered dispatch** — on layouts with a front/refine split
  (``CompiledPlan.supports_split``), batch N+1's candidate-generation
  stage (``run_front``) is enqueued *before* batch N's refine + rerank
  (``run_finish``) is retired, overlapping the HBM-resident front with
  the CXL/SSD-bound refine exactly as the paper's pipeline does for
  levels.  The virtual-clock model mirrors that: a front unit and a
  refine unit with independent free times, each batch's stage times
  taken from its own ledger (front = HBM tier seconds, refine = the
  rest).  The fused sharded body has no split point; it dispatches
  whole batches on a single serial unit.
* **Per-tenant QoS** — each tenant owns a token bucket
  (``rate_rps``/``burst``).  A request arriving to an empty bucket is
  *degraded, not rejected*: it runs under a reduced
  ``QueryPlan.refine_budget`` (÷ ``degrade_factor``, floored at k) and
  its response carries ``degraded=True``.  Throttling trades recall
  for admission — the starved tenant still progresses.
* **Result cache** (``serving.cache.ResultCache``) — admission first
  probes the cache under the exact class plan the request would run
  with; hits bypass the coalescer entirely and are charged a fixed
  ``hit_latency_us``.  Entries key on (quantized query bytes, resolved
  plan, index generation) and are purged by ``StreamingIndex``
  mutations via the generation hook.

Bit-identity: batches are formed only within a service class, padded
rows are masked out of candidates and counters by ``qvalid``, and the
datapath is per-query deterministic — so every response's ids,
distances, and the summed ledger are bit-identical to sequential
``db.query`` calls with the same per-request plans (pinned in
``tests/test_serving.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.anns.api import Database, QueryPlan, SearchResult
from repro.anns.executor import bucket_for, pad_chunk
from repro.anns.pipeline import FaTRQIndex
from repro.memory.tiers import QueryCost, Tier
from repro.models.model_zoo import ModelApi
from repro.obs import metrics as obs_metrics, trace
from repro.obs.metrics import MetricsRegistry
from repro.serving.cache import ResultCache, query_key

__all__ = ["Request", "Response", "TenantQoS", "TokenBucket",
           "VirtualClock", "ServingEngine", "ServingStats",
           "Engine", "ServeStats", "Retriever", "RagResult", "rag_answer"]


@dataclass(frozen=True)
class Request:
    """One serving request: a single query vector plus scheduling
    metadata.  ``rid`` is assigned by the engine (monotonic, arrival
    order) when left ``None``."""

    query: object                      # (D,) float vector
    tenant: str = "default"
    k: int | None = None               # None → plan/config final_k
    arrival_us: float = 0.0
    deadline_us: float = math.inf
    rid: int | None = None


@dataclass
class Response:
    """One completed request.  ``cost`` is the ledger of the *batch* the
    request rode in (shared object across its co-batched peers; None for
    cache hits, which never touch the datapath)."""

    rid: int
    tenant: str
    ids: np.ndarray
    distances: np.ndarray
    degraded: bool
    cache_hit: bool
    arrival_us: float
    admit_us: float
    done_us: float
    batch: int | None
    cost: QueryCost | None

    @property
    def latency_us(self) -> float:
        return self.done_us - self.arrival_us


@dataclass
class VirtualClock:
    """Deterministic microsecond clock; only ever advances."""

    now_us: float = 0.0

    def advance_to(self, t_us: float) -> None:
        self.now_us = max(self.now_us, t_us)


@dataclass
class TokenBucket:
    """Standard token bucket in request units, refilled on observation."""

    rate_per_s: float
    burst: float
    tokens: float = 0.0
    last_us: float = 0.0

    def __post_init__(self):
        self.tokens = self.burst

    def _refill(self, now_us: float) -> None:
        if now_us > self.last_us:
            self.tokens = min(
                self.burst,
                self.tokens + (now_us - self.last_us) * self.rate_per_s / 1e6)
            self.last_us = now_us

    def peek(self, now_us: float) -> bool:
        """True when a full-service token is available (does not consume)."""
        self._refill(now_us)
        return self.tokens >= 1.0

    def take(self, now_us: float) -> None:
        self._refill(now_us)
        self.tokens -= 1.0


@dataclass(frozen=True)
class TenantQoS:
    """Per-tenant service contract: sustained full-service rate and burst
    allowance.  ``rate_rps=None`` means unthrottled (never degraded)."""

    rate_rps: float | None = None
    burst: float = 8.0


@dataclass
class ServingStats:
    requests: int = 0
    batches: int = 0
    cache_hits: int = 0
    degraded: int = 0
    padded_slots: int = 0

    def as_dict(self) -> dict:
        return {"requests": self.requests, "batches": self.batches,
                "cache_hits": self.cache_hits, "degraded": self.degraded,
                "padded_slots": self.padded_slots}


@dataclass
class _Admitted:
    """A request past admission, waiting in its class queue."""

    deadline_us: float
    arrival_us: float
    rid: int
    req: Request
    admit_us: float
    qkey: bytes | None
    degraded: bool


@dataclass
class _Inflight:
    """A batch whose front stage has been dispatched but whose refine has
    not been retired yet (double buffering holds at most one)."""

    bid: int
    batch: list
    cp: object
    qpad: object
    cand: object
    n: int
    dispatch_us: float
    degraded: bool


class ServingEngine:
    """Continuous-batching request scheduler over one ``Database``.

    Parameters
    ----------
    index : FaTRQIndex | ShardedIndex | StreamingIndex | Database
    plan : QueryPlan | None — base plan; ``micro_batch`` is forced to
        ``max_batch`` so coalesced batches are single executor chunks.
    max_batch : coalescer close size (and compiled micro-batch).
    max_wait_us : coalescer close age for a non-full batch.
    qos : dict[str, TenantQoS] — per-tenant contracts; missing tenants
        fall back to ``default_qos`` (None = unthrottled).
    degrade_factor : refine-budget divisor for throttled requests.
    cache : ResultCache | None — attach a result cache.
    batching : False degenerates to one-request batches (the baseline
        the benchmark compares against).
    overlap : False disables double buffering (serial timing model).
    dispatch_overhead_us : fixed host cost charged per dispatched batch
        in the virtual timing model — the submit + sync round trip the
        tier ledger (pure memory traffic) cannot see.  This is the cost
        coalescing amortizes: one-request batches pay it per query.
    """

    def __init__(self, index, *, plan: QueryPlan | None = None,
                 max_batch: int = 8, max_wait_us: float = 200.0,
                 qos: dict | None = None,
                 default_qos: TenantQoS | None = None,
                 degrade_factor: int = 4,
                 cache: ResultCache | None = None,
                 batching: bool = True, overlap: bool = True,
                 dispatch_overhead_us: float = 50.0,
                 mesh=None, tracer=None):
        self.db = index if isinstance(index, Database) else Database.wrap(index)
        if not batching:
            max_batch, max_wait_us = 1, 0.0
        self.max_batch = int(max_batch)
        self.max_wait_us = float(max_wait_us)
        base = plan or QueryPlan()
        base = dataclasses.replace(base, micro_batch=self.max_batch)
        self.base_plan = self.db.validate(base)
        self.qos = dict(qos or {})
        self.default_qos = default_qos
        self.degrade_factor = int(degrade_factor)
        self.cache = cache
        self.overlap = bool(overlap)
        self.dispatch_overhead_us = float(dispatch_overhead_us)
        self.mesh = mesh
        if cache is not None:
            cache.attach(self.db.index)

        self.clock = VirtualClock()
        self.stats = ServingStats()
        self.total_cost = QueryCost()
        self.batch_log: list[tuple] = []   # (bid, dispatch_us, rids)
        self._buckets: dict[str, TokenBucket] = {}
        self._queues: dict[tuple, list] = {}    # (k, degraded) -> [_Admitted]
        self._plan_cache: dict[tuple, QueryPlan] = {}
        self._inflight: _Inflight | None = None
        self._next_rid = 0
        # virtual pipeline units (see module docstring)
        self._front_free_us = 0.0
        self._refine_free_us = 0.0
        self._busy_free_us = 0.0

        # observability: a per-engine metrics registry (activated while the
        # engine runs, so series recorded beneath it aggregate here, not in
        # the process default) + an optional tracer whose virtual clock is
        # wired to the engine's.
        self.registry = MetricsRegistry()
        self.tracer = tracer
        if tracer is not None and tracer.virtual_clock is None:
            tracer.virtual_clock = lambda: self.clock.now_us
        self._m_requests = self.registry.counter(
            "serving_requests_total", "requests admitted, by tenant",
            labelnames=("tenant",))
        self._m_throttled = self.registry.counter(
            "serving_throttled_total",
            "requests degraded by QoS throttling, by tenant",
            labelnames=("tenant",))
        self._m_queue_wait = self.registry.histogram(
            "serving_queue_wait_us",
            "virtual µs between admission and batch dispatch")
        self._m_occupancy = self.registry.histogram(
            "serving_batch_occupancy", "requests per dispatched batch",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0))
        self.registry.add_collector(self._mirror_stats)
        if cache is not None:
            cache.bind_metrics(self.registry)

    def _mirror_stats(self) -> None:
        """Export-time collector: ``ServingStats`` snapshot → the
        ``serving_stats{field=...}`` gauge family."""
        g = self.registry.gauge("serving_stats", "ServingStats snapshot",
                                labelnames=("field",))
        for name, v in self.stats.as_dict().items():
            g.labels(field=name).set(v)

    def metrics(self) -> dict:
        """One flat ``{"name{labels}": value}`` dict unifying scheduler
        counters, ServingStats, per-tenant throttling, cache stats, and
        any datapath series recorded while ``run`` was active."""
        return self.registry.flat()

    # -- QoS ---------------------------------------------------------------

    def _bucket(self, tenant: str) -> TokenBucket | None:
        contract = self.qos.get(tenant, self.default_qos)
        if contract is None or contract.rate_rps is None:
            return None
        bucket = self._buckets.get(tenant)
        if bucket is None:
            bucket = TokenBucket(rate_per_s=contract.rate_rps,
                                 burst=contract.burst,
                                 last_us=self.clock.now_us)
            self._buckets[tenant] = bucket
        return bucket

    def _class_plan(self, k: int, degraded: bool) -> QueryPlan:
        """The resolved plan a (k, degraded) service class runs under.
        Degraded classes trade refine depth (÷ degrade_factor, floored at
        k so the rerank stage stays well-formed) for admission."""
        key = (k, degraded)
        plan = self._plan_cache.get(key)
        if plan is None:
            rb = self.base_plan.refine_budget
            if degraded:
                rb = max(k, rb // self.degrade_factor)
            plan = self.db.validate(dataclasses.replace(
                self.base_plan, k=k, refine_budget=rb))
            self._plan_cache[key] = plan
        return plan

    # -- admission ---------------------------------------------------------

    def _admit(self, req: Request, responses: list) -> None:
        now = self.clock.now_us
        self.stats.requests += 1
        self._m_requests.labels(tenant=req.tenant).inc()
        rk = req.k or self.base_plan.k
        bucket = self._bucket(req.tenant)
        degraded = bucket is not None and not bucket.peek(now)
        trace.event("serve.admit", track="sched", rid=req.rid,
                    tenant=req.tenant, k=rk, degraded=degraded)
        if degraded:
            self._m_throttled.labels(tenant=req.tenant).inc()
            trace.event("serve.throttle", track="sched", rid=req.rid,
                        tenant=req.tenant)
        plan = self._class_plan(rk, degraded)
        qkey = None
        if self.cache is not None:
            qkey = query_key(req.query)
            entry = self.cache.lookup(qkey, plan, self.db.generation)
            if entry is not None:
                self.stats.cache_hits += 1
                if degraded:
                    self.stats.degraded += 1
                trace.event("serve.cache_hit", track="sched", rid=req.rid,
                            tenant=req.tenant)
                responses.append(Response(
                    rid=req.rid, tenant=req.tenant,
                    ids=entry.ids.copy(), distances=entry.distances.copy(),
                    degraded=degraded, cache_hit=True,
                    arrival_us=req.arrival_us, admit_us=now,
                    done_us=now + self.cache.hit_latency_us,
                    batch=None, cost=None))
                return
        if degraded:
            self.stats.degraded += 1
        elif bucket is not None:
            bucket.take(now)    # full service consumes; misses only
        self._queues.setdefault((rk, degraded), []).append(_Admitted(
            deadline_us=req.deadline_us, arrival_us=req.arrival_us,
            rid=req.rid, req=req, admit_us=now, qkey=qkey,
            degraded=degraded))

    # -- coalescing + dispatch ---------------------------------------------

    def _dispatch_ready(self, responses: list, *, drain: bool = False) -> None:
        now = self.clock.now_us
        for class_key in list(self._queues):
            queue = self._queues[class_key]
            while queue:
                oldest = min(a.admit_us for a in queue)
                full = len(queue) >= self.max_batch
                aged = now >= oldest + self.max_wait_us
                if not (full or aged or drain):
                    break
                # EDF within the class: earliest deadline first, then
                # arrival, then rid — a total, deterministic order.
                queue.sort(key=lambda a: (a.deadline_us, a.arrival_us, a.rid))
                batch, self._queues[class_key] = (
                    queue[:self.max_batch], queue[self.max_batch:])
                queue = self._queues[class_key]
                self._dispatch(class_key, batch, responses)
            if not self._queues[class_key]:
                del self._queues[class_key]

    def _dispatch(self, class_key: tuple, batch: list, responses: list) -> None:
        rk, degraded = class_key
        bid = len(self.batch_log)
        now = self.clock.now_us
        self.batch_log.append((bid, now, tuple(a.rid for a in batch)))
        self.stats.batches += 1
        self._m_occupancy.observe(len(batch))
        for a in batch:
            self._m_queue_wait.observe(now - a.admit_us)
        n = len(batch)
        with trace.span("serve.dispatch", track="sched", bid=bid, k=rk,
                        degraded=degraded, n=n, rids=[a.rid for a in batch]):
            cp = self.db.compiled(self._class_plan(rk, degraded),
                                  mesh=self.mesh)
            q = jnp.stack([jnp.asarray(a.req.query, jnp.float32)
                           for a in batch])
            split = self.overlap and cp.supports_split
            if split:
                bucket = bucket_for(n, self.max_batch)
                qpad, qvalid = pad_chunk(q, bucket)
                self.stats.padded_slots += bucket - n
                cand = cp.run_front(qpad, qvalid=qvalid)
        # retire the PREVIOUS batch's refine only after this front is
        # enqueued — the double buffer.
        self._retire_inflight(responses)
        if split:
            self._inflight = _Inflight(bid=bid, batch=batch, cp=cp,
                                       qpad=qpad, cand=cand, n=n,
                                       dispatch_us=now, degraded=degraded)
        else:
            with trace.span("serve.retire", track="sched", bid=bid):
                res = cp.execute(q, pad=True)   # executor buckets internally
                self.stats.padded_slots += bucket_for(n, self.max_batch) - n
                self._complete(bid, batch, cp, res, n, now, degraded,
                               responses, split=False)

    def _retire_inflight(self, responses: list) -> None:
        fl = self._inflight
        if fl is None:
            return
        self._inflight = None
        with trace.span("serve.retire", track="sched", bid=fl.bid):
            res = fl.cp.run_finish(fl.qpad, fl.cand)
            self._complete(fl.bid, fl.batch, fl.cp, res, fl.n,
                           fl.dispatch_us, fl.degraded, responses,
                           split=True)

    # -- completion --------------------------------------------------------

    def _complete(self, bid: int, batch: list, cp, res, n: int,
                  dispatch_us: float, degraded: bool, responses: list,
                  *, split: bool) -> None:
        cost = res.cost
        front_s = cost.tier_seconds(Tier.HBM)
        # per-batch host dispatch round trip rides on the front stage —
        # this is the fixed cost the coalescer amortizes over the batch
        f_us = front_s * 1e6 + self.dispatch_overhead_us
        r_us = max(cost.total_seconds() - front_s, 0.0) * 1e6
        tr = trace.active()
        if self.overlap and split:
            start_f = max(dispatch_us, self._front_free_us)
            front_done = start_f + f_us
            self._front_free_us = front_done
            start_r = max(front_done, self._refine_free_us)
            done = start_r + r_us
            self._refine_free_us = done
            if tr is not None:
                # the units' occupancy is known only now — spans are
                # back-stamped with explicit virtual intervals
                sp = tr.add_span("serve.batch", track="sched",
                                 virtual_start_us=dispatch_us,
                                 virtual_end_us=done, bid=bid, n=n,
                                 degraded=degraded, split=True)
                tr.add_span("serve.front", track="unit:front",
                            virtual_start_us=start_f,
                            virtual_end_us=front_done,
                            parent=sp.sid, bid=bid)
                tr.add_span("serve.refine", track="unit:refine",
                            virtual_start_us=start_r, virtual_end_us=done,
                            parent=sp.sid, bid=bid)
        else:
            start = max(dispatch_us, self._busy_free_us)
            done = start + f_us + r_us
            self._busy_free_us = done
            if tr is not None:
                sp = tr.add_span("serve.batch", track="sched",
                                 virtual_start_us=dispatch_us,
                                 virtual_end_us=done, bid=bid, n=n,
                                 degraded=degraded, split=False)
                tr.add_span("serve.dispatch.serial", track="unit:serial",
                            virtual_start_us=start, virtual_end_us=done,
                            parent=sp.sid, bid=bid)
        self.total_cost.merge(cost)
        with trace.span("wait", track="sched", bid=bid):
            ids = np.asarray(res.ids[:n])
            dists = np.asarray(res.distances[:n])
        for i, adm in enumerate(batch):
            if self.cache is not None and adm.qkey is not None:
                self.cache.insert(adm.qkey, cp.plan, cp.generation,
                                  ids[i], dists[i], degraded=degraded)
            responses.append(Response(
                rid=adm.rid, tenant=adm.req.tenant,
                ids=ids[i], distances=dists[i],
                degraded=degraded, cache_hit=False,
                arrival_us=adm.arrival_us, admit_us=adm.admit_us,
                done_us=done, batch=bid, cost=cost))

    # -- event loop --------------------------------------------------------

    def run(self, requests: list) -> list:
        """Run a full request trace to drain; responses in rid order.

        Discrete-event loop: the clock jumps between arrival instants and
        coalescer close deadlines — nothing happens between events, so
        the simulation is exact and deterministic.

        The engine's metrics registry is active for the duration (and the
        engine's tracer, when one was attached), so datapath series and
        spans recorded deep in the executor land with the engine's own.
        """
        with self._observed():
            return self._run(requests)

    @contextlib.contextmanager
    def _observed(self):
        with contextlib.ExitStack() as stack:
            stack.enter_context(obs_metrics.use(self.registry))
            if self.tracer is not None:
                stack.enter_context(trace.use(self.tracer))
            yield

    def _run(self, requests: list) -> list:
        pending = sorted(
            requests,
            key=lambda r: (r.arrival_us,
                           r.rid if r.rid is not None else math.inf))
        pending = [r if r.rid is not None
                   else dataclasses.replace(r, rid=self._fresh_rid())
                   for r in pending]
        responses: list[Response] = []
        i = 0
        while i < len(pending) or self._queues:
            times = []
            if i < len(pending):
                times.append(pending[i].arrival_us)
            for queue in self._queues.values():
                oldest = min(a.admit_us for a in queue)
                times.append(oldest + self.max_wait_us)
            self.clock.advance_to(min(times))
            now = self.clock.now_us
            arrivals = []
            while i < len(pending) and pending[i].arrival_us <= now:
                arrivals.append(pending[i])
                i += 1
            # EDF admission order at this instant.
            arrivals.sort(key=lambda r: (r.deadline_us, r.arrival_us, r.rid))
            if arrivals:
                with trace.span("serve.admit", track="sched",
                                n=len(arrivals)):
                    for req in arrivals:
                        self._admit(req, responses)
            self._dispatch_ready(responses)
        self._dispatch_ready(responses, drain=True)
        self._retire_inflight(responses)
        responses.sort(key=lambda r: r.rid)
        return responses

    def _fresh_rid(self) -> int:
        rid = self._next_rid
        self._next_rid += 1
        return rid

    def serve(self, queries, *, k: int | None = None,
              tenant: str = "default") -> list:
        """Convenience: submit one request per row at the current clock
        instant and run to drain.  Responses come back in input order."""
        with self._observed(), \
                trace.span("serve", track="sched", n=len(queries)):
            with trace.span("serve.requests", track="sched"):
                queries = jnp.asarray(queries, jnp.float32)
                now = self.clock.now_us
                reqs = [Request(query=queries[i], tenant=tenant, k=k,
                                arrival_us=now, rid=self._fresh_rid())
                        for i in range(queries.shape[0])]
            return self._run(reqs)


# ----------------------------------------------------------- RAG serving
# The LM-facing half of the serving layer (formerly ``serving.engine``,
# absorbed here so the package has ONE serving entry point): a minimal
# batched decode engine, the planned ``Retriever`` wrapper over
# ``Database``, and the ``rag_answer`` round-trip coupling the two
# (paper Fig. 1: embed prompt → ANNS → feed retrieved context to the LM).


@dataclass
class ServeStats:
    steps: int = 0
    tokens: int = 0
    retrievals: int = 0


class Engine:
    """Minimal batched decode engine (greedy)."""

    def __init__(self, api: ModelApi, params, *, batch: int, max_len: int,
                 dtype=jnp.float32):
        self.api = api
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.cache = api.init_cache(params, batch, max_len, dtype)
        self.stats = ServeStats()

    def prefill(self, batch_inputs: dict) -> None:
        if self.api.prefill is not None:
            self.cache = self.api.prefill(self.params, batch_inputs,
                                          self.cache)

    def decode(self, tokens: jax.Array, steps: int) -> jax.Array:
        """tokens (B, 1) seed; returns (B, steps) greedy continuations."""
        out = []
        cur = tokens
        for _ in range(steps):
            logits, self.cache = self.api.decode_step(self.params, cur,
                                                      self.cache)
            cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            out.append(cur[:, 0])
            self.stats.steps += 1
            self.stats.tokens += self.batch
        return jnp.stack(out, axis=1)


@dataclass
class Retriever:
    """Serving-side wrapper over the ``anns.api.Database`` handle: one
    default ``QueryPlan`` + a running traffic ledger.

    ``total_cost`` accumulates traffic across requests (capacity-planning
    view); each ``retrieve`` also returns the per-call QueryCost.

    The per-field knobs (``front``/``backend``/``micro_batch``/``shards``)
    are the legacy surface and become the default plan; pass ``plan=`` to
    override them wholesale.  Both registered fronts (IVF and graph) run
    on every index layout; the plan is still validated once against the
    capability registry (invalid plans — unknown names, a shard count or
    front mismatching a wrapped ``ShardedIndex`` — raise ``anns.PlanError``
    at plan time) and compiled once into an executor cached per (index
    generation, plan): repeated ``retrieve`` calls reuse it, and a
    ``StreamingIndex``'s ``insert``/``delete``/``compact``/``rebalance``
    generation bumps invalidate it, including the sharded snapshot behind
    ``shards=S``.

    ``index`` may be a ``FaTRQIndex``, ``ShardedIndex``, ``StreamingIndex``
    or ``TieredIndex`` (or a ready ``Database``): streaming retrieval
    returns stable global ids across compactions and bills delta-list
    traffic to the running ledger's distinct ``delta:cxl`` entry; sharded
    retrieval arrives pre-folded under the parallel-shard model (max time
    across shards, summed bytes); tiered retrieval bills hot/cold
    placement traffic to ``hot:hbm``/``cold:ssd`` and its
    ``rebalance_tiers()`` generation bumps invalidate cached executors
    exactly like streaming mutations do.
    """

    index: "FaTRQIndex | StreamingIndex | Database"    # noqa: F821
    front: str = "ivf"
    backend: str = "reference"
    micro_batch: int | None = 8
    shards: int | None = None
    plan: QueryPlan | None = None
    bucket: bool = True
    total_cost: QueryCost = field(default_factory=QueryCost)

    @property
    def db(self) -> Database:
        return Database.wrap(self.index)

    def default_plan(self) -> QueryPlan:
        if self.plan is not None:
            return self.plan
        return QueryPlan(front=self.front, backend=self.backend,
                         shards=self.shards, micro_batch=self.micro_batch)

    def retrieve(self, queries: jax.Array, *, k: int,
                 micro_batch: int | None = None
                 ) -> tuple[jax.Array, QueryCost]:
        """Legacy tuple surface: (Q, k) ids + per-call ledger.
        ``micro_batch`` overrides the plan's batching for this call."""
        res = self.query(queries, k=k, micro_batch=micro_batch)
        return res.ids, res.cost

    def query(self, queries: jax.Array, *, k: int,
              micro_batch: int | None = None) -> SearchResult:
        """Planned retrieval → ``SearchResult`` (ids, exact distances,
        ledger, resolved plan); folds the call into ``total_cost``.

        With ``bucket=True`` (the default) ragged trailing chunks pad to
        the smallest compiled power-of-two bucket ≤ the micro-batch and
        mask the padding with ``qvalid`` — so serving a stream of varying
        batch sizes reuses the handful of bucket traces instead of
        compiling one per distinct remainder (padded rows contribute
        neither candidates nor ledger traffic; results are bit-identical
        to the unpadded path)."""
        res = self.db.query(queries, plan=self.default_plan(), k=k,
                            micro_batch=micro_batch, bucket=self.bucket)
        self.total_cost.merge(res.cost)
        return res


class RagResult(NamedTuple):
    """The full RAG round-trip output: generated tokens, retrieved ids,
    the retrieval traffic ledger, and whether QoS throttling degraded any
    of the batch's retrievals (always False outside a ``ServingEngine``)."""

    tokens: jax.Array     # (B, decode_steps) greedy continuations
    ids: jax.Array        # (B, k) retrieved context ids
    cost: QueryCost       # retrieval ledger for this call
    degraded: bool        # any retrieval ran under a degraded QoS plan


def rag_answer(engine: Engine, index: FaTRQIndex, embed_fn, prompt_tokens,
               *, k: int = 5, decode_steps: int = 8,
               retriever: Retriever | None = None, micro_batch: int = 8,
               plan: QueryPlan | None = None,
               serving=None) -> RagResult:
    """One RAG round-trip: embed the prompt, FaTRQ-retrieve top-k context
    ids through the planned ``Database`` datapath (micro-batched), prepend
    them (stub tokenization: ids mod vocab), decode.

    ``plan`` threads the caller's full ``QueryPlan`` (shards, backend,
    refine budget, ...) into the default retriever — previously a default
    ``Retriever`` was constructed that silently ignored any such
    configuration.  Pass ``retriever`` instead to keep a running ledger
    across calls, or ``serving`` (a ``ServingEngine``) to route retrieval
    through the continuous-batching scheduler — QoS degradation and cache
    hits then surface in the returned ``RagResult`` (``degraded`` flag;
    cache hits contribute no ledger traffic).  The three are mutually
    exclusive.

    Returns a ``RagResult`` named tuple — the retrieval ``QueryCost`` and
    the ``degraded`` flag ride along with tokens and ids, so callers
    (e.g. ``launch.serve``) can bill retrieval traffic per request
    without reaching into retriever internals."""
    q = embed_fn(prompt_tokens)                       # (B, D) embeddings
    if serving is not None:
        if retriever is not None or plan is not None:
            raise ValueError("pass serving= alone — a ServingEngine "
                             "carries its own plan and QoS config")
        resp = serving.serve(q, k=k)
        ids = jnp.asarray(np.stack([r.ids for r in resp]))
        cost = QueryCost()
        seen_batches = set()
        for r in resp:
            if r.cost is not None and r.batch not in seen_batches:
                seen_batches.add(r.batch)
                cost.merge(r.cost)
        degraded = any(r.degraded for r in resp)
    else:
        if retriever is None:
            if plan is not None and plan.micro_batch is None:
                plan = dataclasses.replace(plan, micro_batch=micro_batch)
            retriever = Retriever(index=index, micro_batch=micro_batch,
                                  plan=plan)
        elif plan is not None:
            raise ValueError("pass plan= or retriever=, not both — a "
                             "Retriever carries its own plan")
        ids, cost = retriever.retrieve(q, k=k)
        degraded = False
    engine.stats.retrievals += q.shape[0]
    # stub contextualization: retrieved ids become context tokens
    ctx = (ids % engine.api.cfg.vocab).astype(jnp.int32)
    seed = jnp.concatenate([ctx, prompt_tokens], axis=1)[:, -1:]
    gen = engine.decode(seed, decode_steps)
    return RagResult(tokens=gen, ids=ids, cost=cost, degraded=degraded)
