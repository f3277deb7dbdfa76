"""From the profiler's trace (``.xplane.pb``) to numbers.

A reader of the XSpace protobuf's wire format written out here (no
TensorFlow, no xprof), because the scope of a device operation — the
``tf_op`` the compiler keeps from ``jax.named_scope``, e.g.
``jit(fn)/jvp(Convolution:conv0_fwd)/conv_general_dilated:`` — sits in the
event's *metadata*, which ``jax.profiler.ProfileData`` does not hand out.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Ops`` (every operation the core
ran, one event each), ``XLA Modules`` (one event per program run), ``Steps``
and ``Async XLA Ops`` (copies and collectives in flight, which overlap the
operations and are no busy time of the core); and the host plane
``/host:CPU`` whose lines are threads, with the harness's spans as events
named ``perf:<span>``.
"""
import glob
import os
import re

# field numbers of tsl/profiler/protobuf/xplane.proto
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_ID, _META_NAME, _META_STATS = 1, 2, 5
_STAT_META_ID, _STAT_DOUBLE, _STAT_UINT, _STAT_INT, _STAT_STR, _STAT_BYTES, \
    _STAT_REF = 1, 2, 3, 4, 5, 6, 7

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
SPAN_PREFIX = "perf:"


def _fields(buf, pos, end):
    """(field number, wire type, value) of one message; a length-delimited
    value is its (start, end) in ``buf``."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, 0, val
        elif wire == 2:
            n = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                n |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, 2, (pos, pos + n)
            pos += n
        elif wire == 1:
            yield key >> 3, 1, (pos, pos + 8)
            pos += 8
        elif wire == 5:
            yield key >> 3, 5, (pos, pos + 4)
            pos += 4
        else:
            raise ValueError("wire type %d in an xplane file" % wire)


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """(key, (start, end) of the value message) of one map<int64, Message>."""
    key, val = 0, None
    for no, _, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            val = v
    return key, val


class Plane:
    """One plane: its lines' events as (name, start ps, duration ps, scope,
    category) rows; scope and category come from the event's metadata."""

    def __init__(self, name):
        self.name = name
        self.lines = {}


def _read_plane(buf, span, want_lines):
    name, lines, metas, stat_names = None, [], [], {}
    for no, _, v in _fields(buf, *span):
        if no == _PLANE_NAME:
            name = _text(buf, v)
        elif no == _PLANE_LINES:
            lines.append(v)
        elif no == _PLANE_EVENT_META:
            metas.append(v)
        elif no == _PLANE_STAT_META:
            key, val = _map_entry(buf, v)
            for n2, _, v2 in _fields(buf, *val):
                if n2 == 2:
                    stat_names[key] = _text(buf, v2)
    if name is None or want_lines(name) is None:
        return None
    keep = want_lines(name)
    meta = {}
    for span_ in metas:
        key, val = _map_entry(buf, span_)
        mname, scope, cat = "", None, None
        for n2, _, v2 in _fields(buf, *val):
            if n2 == _META_NAME:
                mname = _text(buf, v2)
            elif n2 == _META_STATS:
                sid, sval = None, None
                for n3, w3, v3 in _fields(buf, *v2):
                    if n3 == _STAT_META_ID:
                        sid = v3
                    elif n3 == _STAT_STR:
                        sval = _text(buf, v3)
                    elif n3 == _STAT_REF:
                        sval = stat_names.get(v3)
                sname = stat_names.get(sid)
                if sname == "tf_op":
                    scope = sval
                elif sname == "hlo_category":
                    cat = sval
        meta[key] = (mname, scope, cat)
    plane = Plane(name)
    for span_ in lines:
        lname, t0_ns, events = None, 0, []
        for no, _, v in _fields(buf, *span_):
            if no == _LINE_NAME:
                lname = _text(buf, v)
                if not keep(lname):
                    break
            elif no == _LINE_TIMESTAMP_NS:
                t0_ns = v
            elif no == _LINE_EVENTS:
                events.append(v)
        else:
            rows = []
            for ev in events:
                mid = off = dur = 0
                for n2, _, v2 in _fields(buf, *ev):
                    if n2 == _EVENT_META_ID:
                        mid = v2
                    elif n2 == _EVENT_OFFSET_PS:
                        off = v2
                    elif n2 == _EVENT_DURATION_PS:
                        dur = v2
                mname, scope, cat = meta.get(mid, ("", None, None))
                rows.append((mname, t0_ns * 1000 + off, dur, scope, cat))
            plane.lines.setdefault(lname, []).extend(rows)
    return plane


def read(path):
    """The device planes (``XLA Ops``, ``Async XLA Ops``) and the host plane
    (only events named ``perf:<span>``) of one ``.xplane.pb``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())

    def want(plane_name):
        if DEVICE_PLANE.match(plane_name):
            return lambda line: line in (OPS_LINE, ASYNC_LINE)
        if plane_name == HOST_PLANE:
            return lambda line: True
        return None

    planes = []
    for no, _, v in _fields(buf, 0, len(buf)):
        if no == 1:
            p = _read_plane(buf, v, want)
            if p is not None:
                if p.name == HOST_PLANE:
                    spans = [r for rows in p.lines.values() for r in rows
                             if r[0].startswith(SPAN_PREFIX)]
                    p.lines = {"spans": sorted(spans, key=lambda r: r[1])}
                planes.append(p)
    return planes


# ---------------------------------------------------------------- reduction
def union(intervals):
    """Merged, sorted [(start, end)] of intervals that may overlap or nest."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(intervals):
    return sum(e - s for s, e in union(intervals))


class Reduced:
    """``devices``: {chip: rows of ``XLA Ops``}, ``inflight``: {chip: rows of
    ``Async XLA Ops``}, ``spans``: the harness's spans on the host plane, all in
    picoseconds on the trace's clock; ``busy_s``: the union of the operations'
    intervals, averaged over the chips."""

    def __init__(self, planes, chips):
        self.devices, self.inflight, self.spans = {}, {}, []
        for p in planes:
            m = DEVICE_PLANE.match(p.name)
            if m:
                self.devices[int(m.group(1))] = p.lines.get(OPS_LINE, [])
                self.inflight[int(m.group(1))] = p.lines.get(ASYNC_LINE, [])
            elif p.name == HOST_PLANE:
                self.spans = p.lines["spans"]
        used = sorted(self.devices)[:chips]
        self.busy_ps = {d: covered((r[1], r[1] + r[2])
                                   for r in self.devices[d]) for d in used}
        self.busy_s = (sum(self.busy_ps.values()) / len(used) * 1e-12
                       if used else 0.0)

    def scope_ps(self, pattern, device=None):
        """Device time (union, so a nested event is not counted twice) of
        the operations whose scope matches ``pattern``, on the busiest chip
        unless one is named."""
        rx = re.compile(pattern)
        best = 0
        for d, rows in self.devices.items():
            if device is not None and d != device:
                continue
            best = max(best, covered(
                (r[1], r[1] + r[2]) for r in rows
                if r[3] is not None and rx.search(r[3])))
        return best

    def matched(self, pattern):
        rx = re.compile(pattern)
        return any(r[3] is not None and rx.search(r[3])
                   for rows in self.devices.values() for r in rows)


def subtract(intervals, cover):
    """Total length of ``intervals`` (merged) not covered by ``cover``."""
    cover = union(cover)
    total, j = 0, 0
    for s, e in union(intervals):
        at = s
        while j < len(cover) and cover[j][1] <= at:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > at:
                total += cover[k][0] - at
            at = max(at, cover[k][1])
            k += 1
        if at < e:
            total += e - at
    return total


def reduce(trace_dir, chips):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise RuntimeError("the profiler left no .xplane.pb in %s" % trace_dir)
    return Reduced(read(files[-1]), chips)


_OP_TYPE = re.compile(r"\(([A-Za-z_][\w]*):[^()]*\)")
_NAMED = re.compile(r"(Optimizer::\w+|GradSync)")


def scope_group(scope, category):
    """A short name for one operation: ``<OpType> bwd|fwd`` after the atlas
    scope contract (``<OpType>:<node>``, ``Optimizer::<name>``, ``GradSync``),
    else the compiler's own category."""
    if scope:
        m = _NAMED.search(scope)
        if m:
            return m.group(1)
        m = _OP_TYPE.search(scope)
        if m:
            return "%s %s" % (m.group(1),
                              "bwd" if "transpose(" in scope else "fwd")
    return "[%s]" % (category or "unnamed")


def breakdown(reduced):
    """{"device_ops": ten groups of device operations by time,
    "idle_gaps": the device's idle time by what the host was doing}, seconds
    per traced stretch."""
    if not reduced.devices:
        return None
    dev = max(reduced.busy_ps, key=reduced.busy_ps.get)
    groups = {}
    for name, start, dur, scope, cat in reduced.devices[dev]:
        key = scope_group(scope, cat)
        groups[key] = groups.get(key, 0) + dur
    ops = sorted(groups.items(), key=lambda kv: -kv[1])[:10]
    busy = union((r[1], r[1] + r[2]) for r in reduced.devices[dev])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    by_host = {}
    for s, e in gaps:
        best, name = 0, "no span"
        for sp in reduced.spans:
            if sp[1] >= e:
                break
            over = min(e, sp[1] + sp[2]) - max(s, sp[1])
            if over > best:
                best, name = over, sp[0][len(SPAN_PREFIX):]
        by_host[name] = by_host.get(name, 0) + (e - s)
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v * 1e-12] for k, v in ops],
            "idle_gaps": [[k, v * 1e-12] for k, v in idle]}
