"""refine_ms: device time, per dispatched micro-batch, of the refine step: code
and scalar gathers and the fused kernel (anns/stages.py _pallas_refine).

The time of one execution is the union of its operations' intervals; the
metric is their mean over the executions that start in the traced window.
The program is found by the name the trace gives it."""

from chipbench import xtrace

MODULE = "jit__pallas_refine"


def read(ctx):
    runs = xtrace.module_runs(ctx.trace, MODULE, ctx.lo, ctx.hi)
    if not runs:
        return None
    return sum(r.busy for r in runs) / len(runs) / 1e6
