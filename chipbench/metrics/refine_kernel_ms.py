"""refine_kernel_ms: device time, per dispatched micro-batch, of the fused
ternary refine kernel, the ops traced under the ``fatrq.refine.kernel``
scope (anns/stages.py _pallas_refine).

The time is the union of those ops' intervals in the traced window over the
window's micro-batches.  Ops are found by the op path each carries in the
trace (``xscope``), whatever program holds them."""

from chipbench import xscope

SCOPE = "fatrq.refine.kernel"


def read(ctx):
    return xscope.scope_ms(ctx, SCOPE)
