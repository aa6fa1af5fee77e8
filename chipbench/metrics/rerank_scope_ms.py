"""rerank_scope_ms: device time, per dispatched micro-batch, of the exact
rerank of the survivors, the ops traced under the ``fatrq.rerank`` scope
(anns/stages.py _rerank_survivors, _rerank_survivors_tiered).

It reads what ``rerank_ms`` reads while the rerank is a program of its own,
and goes on reading it once the rerank is fused into a larger program: the
time is the union of the scope's op intervals in the traced window over the
window's micro-batches, and ops are found by the op path each carries in
the trace (``xscope``), whatever program holds them."""

from chipbench import xscope

SCOPE = "fatrq.rerank"


def read(ctx):
    return xscope.scope_ms(ctx, SCOPE)
