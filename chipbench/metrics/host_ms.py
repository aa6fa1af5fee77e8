"""host_ms: host time on the critical path, per dispatched micro-batch: the
time of the traced window in which no operation runs on the device while
the serving engine works on the host, inside a ``fatrq.serve`` span and
outside every ``fatrq.wait`` span, on the thread that holds the
benchmark's window annotation.  Averaged over the cell's chips, over the
window's micro-batches.

Host work that overlaps device work costs nothing end to end and is left
out, so is a host blocked in an enqueue while the device runs.  The spans
are the program's own (repro/obs/trace.py), written to the profiler's host
plane."""

from chipbench import xtrace

SERVE = "fatrq.serve"
WAIT = "fatrq.wait"


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def read(ctx):
    lines = {e.line for e in ctx.trace.host if e.name == xtrace.WINDOW}
    host = [e for e in ctx.trace.host if e.line in lines]
    serve = [(e.start, e.end) for e in host if e.name == SERVE]
    if not xtrace.union(serve, ctx.lo, ctx.hi) or not ctx.batches \
            or not ctx.trace.devices:
        return None
    waits = [(e.start, e.end) for e in host if e.name == WAIT]
    total = 0
    for dev in ctx.trace.devices:
        # |serve \ (waits ∪ ops)| = |serve ∪ waits ∪ ops| − |waits ∪ ops|
        covered = waits + [(o.start, o.end) for o in dev.ops]
        total += _length(xtrace.union(serve + covered, ctx.lo, ctx.hi)) \
            - _length(xtrace.union(covered, ctx.lo, ctx.hi))
    return total / len(ctx.trace.devices) / len(ctx.batches) / 1e6
