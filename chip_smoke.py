#!/usr/bin/env python3
"""Chip smoke run: the FaTRQ search path, end to end, on one TPU.

Builds the paper's text-RAG deployment (the Wiki-88M setting of
``repro.data.synthetic``: d=768 f32 unit-norm embeddings in 64 clusters,
L2, k=10, IVF front), cut to 1,000,000 rows and 256 queries generated from
``--seed``, and drives it through the entry points a user calls:

* ``Database.build`` — PQ → IVF → TRQ encode → calibration, on the chip;
* ``Database.query`` — refinement in the fused Pallas kernel, compiled
  (``backend="pallas"``), against the pure-jnp reference backend;
* ``ServingEngine.serve`` — 64 requests through the continuous batcher.

It fails unless the Pallas ids equal the reference ids, recall@10 against
a HIGHEST-precision brute-force reference is at least 0.80, and the served
ids equal ``Database.query``'s.  With ``--chips 4`` it runs only the
sharded search (``shards=4`` over a ``("search",)`` mesh against
``shards=1`` on device 0) and requires equal ids and per-tier ledger bytes.

    python chip_smoke.py [--chips 4] [--seed 0]

Every line but the last is a JSON record of one phase; the timings in them
are smoke timings, not benchmark results.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU it exits non-zero and
prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.anns import (Database, PipelineConfig, QueryPlan,  # noqa: E402
                        recall_at_k, stages)
from repro.data import make_dataset  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402

N_ROWS = 1_000_000
N_QUERIES = 256
N_SERVED = 64
N_CLUSTERS = 64
RECALL_FLOOR = 0.80
CONFIG = PipelineConfig(dim=768, pq_m=96, pq_k=256, nlist=1024, nprobe=16,
                        trq_levels=2, final_k=10, refine_budget=50,
                        bound="cauchy", micro_batch=32)
REDUCED = {"rows": "88M -> 1M: full-precision rows stay in HBM",
           "queries": 256}


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def timed(fn, *args, **kwargs):
    """(result, wall seconds) of fn, blocking on every array it returns."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0


def build(*, n: int, n_queries: int, config: PipelineConfig, seed: int):
    """Generate the data set and build the index on the default device."""
    ds, t_data = timed(make_dataset, jax.random.PRNGKey(seed), n=n,
                       d=config.dim, n_queries=n_queries,
                       k_gt=config.final_k, clusters=N_CLUSTERS)
    t0 = time.perf_counter()
    db = Database.build(jax.random.PRNGKey(seed + 1), ds.x, config)
    idx = db.index
    jax.block_until_ready((idx.pq_codes, idx.ivf, idx.trq, idx.codebook))
    t_build = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    emit("build", rows=n, dim=config.dim, queries=n_queries,
         data_s=t_data, build_s=t_build,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_in_use=stats.get("bytes_in_use"),
         ivf_cap=int(idx.ivf.cap), config=dataclasses.asdict(config),
         reduced=REDUCED)
    return ds, db


def refine_step_text(db: Database, queries) -> str:
    """Compiled text of the jitted refine step the Pallas plan runs for
    one micro-batch."""
    cfg = db.config
    ex = db.executor_for(QueryPlan(backend="pallas"))
    cand = ex.front.candidates(queries)
    return stages._pallas_refine.lower(
        queries, cand.d0, cand.ids, cand.valid, cand.is_delta, db.index.trq,
        k=cfg.final_k, bound=cfg.bound, z=cfg.z,
        block_c=ex.backend.block_c).compile().as_text()


def query_phase(db: Database, ds) -> np.ndarray:
    """Pallas vs reference ``Database.query``; recall against the exact
    reference.  Returns the Pallas ids."""
    cfg = db.config
    mb = cfg.micro_batch
    text = refine_step_text(db, ds.queries[:mb])
    check("tpu_custom_call" in text,
          "the compiled refine step holds no tpu_custom_call (kernel was "
          "interpreted)")
    ids = {}
    for backend in ("pallas", "reference"):
        plan = QueryPlan(backend=backend)
        _, first = timed(lambda q: db.query(q, plan=plan).ids,
                         ds.queries[:mb])
        res, t_all = timed(lambda q: db.query(q, plan=plan).ids, ds.queries)
        ids[backend] = np.asarray(res)
        emit("query", backend=backend, micro_batch=mb,
             smoke_first_call_s=first,
             smoke_warm_s_per_micro_batch=t_all / -(-len(ds.queries) // mb))
    same = np.array_equal(ids["pallas"], ids["reference"])
    recall = recall_at_k(ids["pallas"], ds.gt, cfg.final_k)
    emit("check", kernel_compiled=True, pallas_ids_equal_reference=same,
         recall_at_10=recall, recall_floor=RECALL_FLOOR)
    check(same, "pallas top-k ids differ from reference ids")
    check(recall >= RECALL_FLOOR, f"recall@10 {recall} < {RECALL_FLOOR}")
    return ids["pallas"]


def serve_phase(db: Database, ds, queried: np.ndarray, n: int) -> None:
    """``ServingEngine.serve`` answers n requests with the ids
    ``Database.query`` gave for the same queries."""
    engine = ServingEngine(db, plan=QueryPlan(backend="pallas"),
                           max_batch=db.config.micro_batch)
    resp, t = timed(engine.serve, ds.queries[:n])
    served = np.stack([r.ids for r in resp])
    same = np.array_equal(served, queried[:n])
    emit("serve", requests=n, batches=engine.stats.batches, smoke_wall_s=t,
         served_ids_equal_query=same)
    check(same, "served ids differ from Database.query ids")


def tier_bytes(cost) -> dict:
    out: dict[str, int] = {}
    for key, t in cost.ledger.items():
        tier = key.rsplit(":", 1)[-1]
        out[tier] = out.get(tier, 0) + t.bytes
    return out


def sharded_phase(db: Database, ds, shards: int) -> None:
    """``shards`` over a ("search",) mesh against shards=1 on device 0:
    equal ids and equal per-tier ledger bytes."""
    res = {}
    for s in (shards, 1):
        plan = QueryPlan(shards=s, backend="pallas")
        _, first = timed(lambda q: db.query(q, plan=plan).ids, ds.queries)
        r, warm = timed(db.query, ds.queries, plan=plan)
        res[s] = r
        emit("sharded_query", shards=s, backend="pallas",
             smoke_first_call_s=first, smoke_warm_s=warm,
             tier_bytes=tier_bytes(r.cost))
    same_ids = np.array_equal(np.asarray(res[shards].ids),
                              np.asarray(res[1].ids))
    same_bytes = tier_bytes(res[shards].cost) == tier_bytes(res[1].cost)
    recall = recall_at_k(res[shards].ids, ds.gt, db.config.final_k)
    emit("check", shards=shards, ids_equal_shards_1=same_ids,
         tier_bytes_equal_shards_1=same_bytes, recall_at_10=recall)
    check(same_ids, f"shards={shards} ids differ from shards=1")
    check(same_bytes, f"shards={shards} tier bytes differ from shards=1")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = compile_cache.enable()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < args.chips:
        sys.exit(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
                 f"{len(devices)} {platform} device(s)")
    emit("start", chips=args.chips, seed=args.seed, compile_cache=cache_dir,
         device_kind=devices[0].device_kind, device_count=len(devices))

    ds, db = build(n=N_ROWS, n_queries=N_QUERIES, config=CONFIG,
                   seed=args.seed)
    if args.chips == 4:
        sharded_phase(db, ds, shards=4)
    else:
        queried = query_phase(db, ds)
        serve_phase(db, ds, queried, N_SERVED)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
