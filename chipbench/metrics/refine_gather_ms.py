"""refine_gather_ms: device time, per dispatched micro-batch, of the gathers
that feed the fused refine kernel: every TRQ level's packed codes and
scalars for the micro-batch's candidates, the ops traced under the
``fatrq.refine.gather`` scope (anns/stages.py _pallas_refine).

The time is the union of those ops' intervals in the traced window over the
window's micro-batches.  Ops are found by the op path each carries in the
trace (``xscope``), whatever program holds them."""

from chipbench import xscope

SCOPE = "fatrq.refine.gather"


def read(ctx):
    return xscope.scope_ms(ctx, SCOPE)
