"""rerank_ms: device time, per dispatched micro-batch, of the exact rerank of
the survivors (anns/stages.py _rerank_survivors).

The time of one execution is the union of its operations' intervals; the
metric is their mean over the executions that start in the traced window.
The program is found by the name the trace gives it."""

from chipbench import xtrace

MODULE = "jit__rerank_survivors"


def read(ctx):
    runs = xtrace.module_runs(ctx.trace, MODULE, ctx.lo, ctx.hi)
    if not runs:
        return None
    return sum(r.busy for r in runs) / len(runs) / 1e6
