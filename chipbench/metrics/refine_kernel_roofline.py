"""refine_kernel_roofline: the fused refine kernel's share of its roofline.

Least time is the larger of bytes over peak HBM bandwidth and operations
over peak rate, for work counted as a lower bound that any implementation
of the refinement must do: for every valid candidate of a micro-batch, read
its level-0 record (⌈D/5⌉ packed ternary bytes and four float32 scalars:
‖δ‖², ⟨x_c, δ⟩, ‖δ‖, ρ) and add its D digits into the inner product.
Deeper levels, padding slots, the coarse distances and the candidate ids
are left out.  A micro-batch's valid candidates are counted here from the
index's shapes: each of its queries probes the ``nprobe`` lists whose
centroids lie nearest in squared L2, and reaches every row of them (the
index's list lengths).  The front's own counter is kept beside it as a
cross-check only.  The share is Σ least time / Σ kernel time over the
window; the kernel is the Pallas call inside the refine program, found by
name.
"""

import re

import numpy as np

from chipbench import xtrace

MODULE = "jit__pallas_refine"
KERNEL = re.compile(r"^ternary_refine_fused(\.\d+)?$")
SCALAR_BYTES = 4 * 4


def bound(dim: int, candidates: int, peak: dict) -> tuple[float, str]:
    """(least seconds, which roof bounds it) for ``candidates`` records."""
    nbytes = candidates * (-(-dim // 5) + SCALAR_BYTES)
    ops = candidates * dim
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["ops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "ops")


def probed_candidates(index: dict, queries: np.ndarray) -> int:
    """Rows that the probes of ``queries`` (n, D) reach: the lengths of each
    query's ``nprobe`` nearest lists, summed."""
    q = np.asarray(queries, np.float64)
    c = index["centroids"]
    d = (np.sum(q * q, 1)[:, None] - 2.0 * q @ c.T
         + np.sum(c * c, 1)[None, :])
    top = np.argpartition(d, index["nprobe"] - 1, axis=1)[:, :index["nprobe"]]
    return int(index["list_len"][top].sum())


def is_kernel(op) -> bool:
    return bool(KERNEL.match(xtrace.op_name(op.name).split(" ")[0]))


def read(ctx):
    runs = xtrace.module_runs(ctx.trace, MODULE, ctx.lo, ctx.hi)
    kernel = [o for r in runs for o in r.ops if is_kernel(o)]
    batches = list(ctx.batches.values())
    if not kernel or len(kernel) != len(batches) or ctx.index is None:
        return None
    cands = [probed_candidates(ctx.index, ctx.queries(b["qidx"]))
             for b in batches]
    least = 0.0
    for c in cands:
        t, which = bound(ctx.config["dim"], c, ctx.peak)
        least += t
    ctx.notes["refine_kernel_bound"] = which
    ctx.notes["refine_kernel_candidates"] = sum(cands)
    ledger = [b.get("front_cand") for b in batches]
    if None not in ledger:
        ctx.notes["refine_kernel_candidates_front_counter"] = sum(ledger)
    return 100.0 * least / (sum(o.dur for o in kernel) / 1e9)
