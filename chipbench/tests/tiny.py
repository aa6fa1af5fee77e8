"""A cell small enough for a CPU test run: the wiki768-1m configuration's
code path and parameters at 4,096 rows of 64 dimensions."""

import copy

from chipbench import loadgen, run

CELL = "wiki768-1m.closed64"
SEED = 2**31 + 77


def parts(**mix) -> dict:
    p = run.cell_parts(run.manifest(), CELL)
    cfg = copy.deepcopy(p["config"])
    cfg.update(rows=4096, dim=64)
    cfg["data"].update(clusters=8, decay_dims=4)
    cfg["pipeline"].update(dim=64, pq_m=8, pq_k=32, nlist=32, nprobe=4,
                           micro_batch=8)
    p["config"] = cfg
    p["mix"] = loadgen.Mix(**{"callers": 16, "max_batch": 8,
                              "backend": "pallas", "warmup_calls": 1,
                              "recall_set": 32, "check_sample": 48, **mix})
    return p
