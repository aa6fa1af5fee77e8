"""Device ops of a profiler trace with the JAX op path each one ran under.

XLA writes each TPU op's HLO metadata into the trace: the event metadata
of an op on a device's ``XLA Ops`` line carries a ``tf_op`` stat, the op
path that JAX gave it (``jit(_ivf_candidates)/fatrq.front.adc/...``),
which holds every ``jax.named_scope`` the op was traced under.
``jax.profiler.ProfileData`` gives the events' own stats but not their
metadata's, so this module reads the raw ``XSpace`` protobuf itself, with
a reader of the protobuf wire format that knows the few fields it needs.
An op is matched to its metadata by the metadata id (op names such as
``fusion.2`` recur across programs).  Start and end are in nanoseconds as
``xtrace.load`` gives them: the line's timestamp plus the event's offset,
and the duration, each truncated from picoseconds.
"""

from __future__ import annotations

import functools
import re
from dataclasses import dataclass

from chipbench import xtrace

# XSpace and the messages under it (tsl/profiler/protobuf/xplane.proto):
# field numbers of the fields read here
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_META, PLANE_STAT_META = 2, 3, 4, 5
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
EVENT_META_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
META_NAME, META_STATS = 2, 5
STAT_META_ID, STAT_STR, STAT_REF = 1, 5, 7
MAP_KEY, MAP_VALUE = 1, 2
TF_OP = "tf_op"


@dataclass(frozen=True)
class Op:
    name: str             # the HLO instruction, as ``xtrace.Event.name``
    start: int            # ns
    end: int              # ns
    tf_op: str            # JAX op path; "" where XLA recorded none


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of a message in buf[lo:hi]: an int for a
    varint, a (start, end) range for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map(buf: bytes, entries: list) -> dict:
    out = {}
    for lo, hi in entries:
        f = dict(_fields(buf, lo, hi))
        out[f.get(MAP_KEY, 0)] = f.get(MAP_VALUE)
    return out


def _plane_ops(buf: bytes, lo: int, hi: int) -> tuple[str, list[Op]]:
    name, lines, ev_meta, st_meta = "", [], [], []
    for f, v in _fields(buf, lo, hi):
        if f == PLANE_NAME:
            name = _text(buf, v)
        elif f == PLANE_LINES:
            lines.append(v)
        elif f == PLANE_EVENT_META:
            ev_meta.append(v)
        elif f == PLANE_STAT_META:
            st_meta.append(v)
    if not xtrace.DEVICE_PLANE.match(name):
        return name, []
    stat_names = {}
    for k, v in _map(buf, st_meta).items():
        span = dict(_fields(buf, *v)).get(META_NAME) if v else None
        if span is not None:
            stat_names[k] = _text(buf, span)
    tf_op_ids = {k for k, n in stat_names.items() if n == TF_OP}
    meta = {}
    for k, v in _map(buf, ev_meta).items():
        op_name, path = "", ""
        for f, sv in _fields(buf, *v):
            if f == META_NAME:
                op_name = _text(buf, sv)
            elif f == META_STATS:
                stat = list(_fields(buf, *sv))
                if dict(stat).get(STAT_META_ID) in tf_op_ids:
                    for sf, val in stat:
                        if sf == STAT_STR:
                            path = _text(buf, val)
                        elif sf == STAT_REF:
                            path = stat_names.get(val, "")
        meta[k] = (op_name, path)
    ops = []
    for lo_l, hi_l in lines:
        fl = list(_fields(buf, lo_l, hi_l))
        if _text(buf, dict(fl).get(LINE_NAME, (0, 0))) != xtrace.OPS_LINE:
            continue
        t0 = dict(fl).get(LINE_TIMESTAMP_NS, 0)
        for f, v in fl:
            if f != LINE_EVENTS:
                continue
            ev = dict(_fields(buf, *v))
            op_name, path = meta.get(ev.get(EVENT_META_ID, 0), ("", ""))
            start = t0 + ev.get(EVENT_OFFSET_PS, 0) // 1000
            ops.append(Op(op_name, start,
                          start + ev.get(EVENT_DURATION_PS, 0) // 1000, path))
    ops.sort(key=lambda o: o.start)
    return name, ops


@functools.lru_cache(maxsize=4)
def load(path: str) -> dict[str, list[Op]]:
    """Device plane name → its ops sorted by start, for the ``.xplane.pb``
    at ``path`` (decoded once per path)."""
    with open(path, "rb") as fh:
        buf = fh.read()
    out = {}
    for f, v in _fields(buf, 0, len(buf)):
        if f == SPACE_PLANES:
            name, ops = _plane_ops(buf, *v)
            if ops:
                out[name] = ops
    return out


def trace_path(ctx) -> str | None:
    """The run's ``.xplane.pb``: ``ctx.xplane`` where a caller gives one,
    else the newest under the benchmark's trace directory, as ``run.py``
    writes it."""
    given = getattr(ctx, "xplane", None)
    if given is not None:
        return given
    from chipbench import run
    return xtrace.find(str(run.CACHE / "trace"))


def under(tf_op: str, scope: str) -> bool:
    """Whether an op path lies under the named scope ``scope``."""
    return re.search(rf"(^|[/(]){re.escape(scope)}($|[/)])", tf_op) \
        is not None


def scope_ms(ctx, scope: str) -> float | None:
    """Device time per micro-batch, in ms, of the ops under ``scope`` that
    start in the window: the union of their intervals clipped to the
    window, averaged over the devices, over the window's micro-batches.
    None where no op lies under the scope or the window has none."""
    path = trace_path(ctx)
    if path is None or not ctx.batches:
        return None
    planes = load(path)
    total, found = 0, False
    for ops in planes.values():
        mine = [(o.start, o.end) for o in ops
                if ctx.lo <= o.start < ctx.hi and under(o.tf_op, scope)]
        found = found or bool(mine)
        total += sum(e - s for s, e in xtrace.union(mine, ctx.lo, ctx.hi))
    if not found:
        return None
    return total / len(planes) / len(ctx.batches) / 1e6
