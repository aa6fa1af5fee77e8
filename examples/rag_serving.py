"""End-to-end RAG serving driver (paper Fig. 1): a small LM answers batched
requests with FaTRQ retrieval in the loop, through the unified ``Database``
API — the caller's ``QueryPlan`` (backend, shards, budget) threads all the
way into the retriever instead of being silently dropped.

    PYTHONPATH=src python examples/rag_serving.py
"""

import jax
import jax.numpy as jnp

from repro.anns import Database, PipelineConfig, QueryPlan
from repro.configs import ARCHS
from repro.data import make_dataset
from repro.models import build_model
from repro.obs import trace
from repro.serving import Engine, Retriever, rag_answer


def main():
    # --- LM: reduced qwen2.5 backbone, batched decode
    cfg = ARCHS["qwen2.5-3b"].reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    engine = Engine(api, params, batch=4, max_len=64)

    # --- retriever: FaTRQ database over the document embedding store;
    # embedding dim = the backbone's hidden size (DESIGN.md §4)
    d = cfg.d_model
    ds = make_dataset(jax.random.PRNGKey(1), n=8_000, d=d, n_queries=4)
    pcfg = PipelineConfig(dim=d, pq_m=16, pq_k=64, nlist=32, nprobe=8,
                          final_k=5, refine_budget=20)
    db = Database.build(jax.random.PRNGKey(2), ds.x, pcfg)

    # the serving plan: validated once against the capability registry,
    # compiled once into a cached executor, reused every request
    plan = QueryPlan(front="ivf", backend="reference", micro_batch=4)
    retriever = Retriever(index=db, plan=plan)

    # embed_fn stub: mean-pool the LM's token embeddings, project to store
    def embed_fn(tokens):
        e = params["embed"][tokens].mean(axis=1)
        return e / jnp.linalg.norm(e, axis=-1, keepdims=True)

    prompts = jax.random.randint(jax.random.PRNGKey(3), (4, 8), 0,
                                 cfg.vocab)
    print("serving 4 batched RAG requests...")
    tracer = trace.Tracer()
    with trace.use(tracer):
        res = rag_answer(engine, db.index, embed_fn, prompts,
                         k=5, decode_steps=8, retriever=retriever)
    print(f"  resolved plan: {retriever.default_plan().resolve(pcfg)}")
    print(f"  retrieved ids (per request): {res.ids.tolist()}")
    print(f"  generated tokens: {res.tokens.tolist()}")
    print(f"  degraded by QoS: {res.degraded}")
    print(f"  retrieval cost breakdown: "
          f"{ {k: f'{v * 1e6:.1f}us' for k, v in res.cost.breakdown().items()} }")
    print(f"  running ledger (capacity view): "
          f"{ {k: t.accesses for k, t in retriever.total_cost.ledger.items()} }")
    print(f"  engine stats: {engine.stats}")

    # --- per-stage ledger bytes from the spans the retrieval just traced:
    # the folded Table-I ledger rides on each ``execute`` span (the
    # device's own time per layer comes from a profiler trace, not here)
    print("per-stage ledger bytes (traced):")
    for sp in tracer.by_name("execute"):
        per_stage: dict[str, int] = {}
        for key, (_, nbytes) in sp.attrs.get("ledger", {}).items():
            stage = key.split(":", 1)[0]
            per_stage[stage] = per_stage.get(stage, 0) + nbytes
        for stage, nbytes in per_stage.items():
            print(f"  {stage:>8}: {nbytes:12,d} B")

if __name__ == "__main__":
    main()
