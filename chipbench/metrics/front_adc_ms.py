"""front_adc_ms: device time, per dispatched micro-batch, of the front's PQ-
ADC lookup: the gather of the candidates' PQ codes and their table scoring,
the ops traced under the ``fatrq.front.adc`` scope (anns/stages.py
adc_score).

The time is the union of those ops' intervals in the traced window over the
window's micro-batches.  Ops are found by the op path each carries in the
trace (``xscope``), whatever program holds them."""

from chipbench import xscope

SCOPE = "fatrq.front.adc"


def read(ctx):
    return xscope.scope_ms(ctx, SCOPE)
