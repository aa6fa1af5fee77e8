"""front_probe_ms: device time, per dispatched micro-batch, of the front's
IVF probe: the centroid ranking and the gather of the probed lists, the
ops traced under the ``fatrq.front.probe`` scope (anns/stages.py
_ivf_candidates).

The time is the union of those ops' intervals in the traced window over the
window's micro-batches.  Ops are found by the op path each carries in the
trace (``xscope``), whatever program holds them."""

from chipbench import xscope

SCOPE = "fatrq.front.probe"


def read(ctx):
    return xscope.scope_ms(ctx, SCOPE)
