"""The program's own spans and device scopes in a profiler trace.

``devtrace.load`` keeps the harness's spans and each device op's
instruction name.  The program under test writes more into the same trace:

* host spans through ``repro.core.tracer.span``, one of each per loop
  iteration on the serving thread: ``serve.plan`` (admission, chunk
  planning), ``serve.blocks`` (block allocation, copy-on-write copies), a
  dispatch span (``serve.unified_step`` or ``serve.spec_step``: inputs
  packed and uploaded, the step enqueued; stats ``steps``,
  ``decode_rows``, ``chunk_tokens``), ``serve.fetch`` (the blocking
  fetch of a step's tokens) and ``serve.retire`` (token bookkeeping,
  retirement, requeueing of preempted requests);
* ``jax.named_scope`` names inside the step (``decode``, ``chunk``, and
  ``kv_write`` around every write into the paged KV pool), which reach
  each device op as its op-name path: the ``tf_op`` stat of the op's
  event metadata on the device plane.  ``jax.profiler.ProfileData`` does
  not expose event metadata, so ``op_scopes`` reads it from the file's
  protobuf wire format (``XSpace`` of ``tsl/profiler/protobuf/xplane.proto``).

``load`` keeps both in a ``devtrace.Trace`` with one more field, so every
devtrace reduction reads it as it reads the harness's trace.  Where the
program wrote no such span or scope (a program older than them), the
reductions here return None.
"""
from __future__ import annotations

import dataclasses

import devtrace

DISPATCH = ("serve.unified_step", "serve.spec_step")
# the serving thread's own work per dispatch; serve.fetch is its wait
HOST_WORK = ("serve.plan", "serve.blocks", *DISPATCH, "serve.retire")
PROGRAM_SPANS = (*HOST_WORK, "serve.fetch")
SCOPE_STAT = "tf_op"


@dataclasses.dataclass
class Trace(devtrace.Trace):
    scopes: list = dataclasses.field(default_factory=list)  # per op: op-name path


def _varint(buf, i: int) -> tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):  # fixed64, fixed32
            n = 8 if kind == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _entry(buf) -> dict:
    return {f: v for f, v in _fields(buf)}


def op_scopes(path: str) -> dict[str, str]:
    """The ``tf_op`` stat of every device op's event metadata, by the
    op's event name.  XSpace.planes = 1; XPlane: name 2, event_metadata 4
    and stat_metadata 5 (map entries: key 1, value 2); XEventMetadata:
    name 2, stats 5; XStatMetadata: id 1, name 2; XStat: metadata_id 1,
    str_value 5, ref_value 7 (a stat-metadata id whose name is the
    string)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                m = _entry(_entry(v).get(2, b""))
                stat_names[m.get(1, 0)] = bytes(m.get(2, b"")).decode()
        if not name.startswith(devtrace.DEVICE_PREFIX):
            continue
        want = {k for k, n in stat_names.items() if n == SCOPE_STAT}
        for v in metas:
            em = _fields(_entry(v).get(2, b""))
            op, scope = "", ""
            for ef, ev in em:
                if ef == 2:
                    op = bytes(ev).decode()
                elif ef == 5:
                    st = _entry(ev)
                    if st.get(1) in want:
                        scope = (bytes(st[5]).decode() if 5 in st
                                 else stat_names.get(st.get(7), ""))
            if scope:
                out[op] = scope
    return out


def load(path: str, span_names: tuple[str, ...] = ()) -> Trace:
    """``devtrace.load``, with the program's spans and each op's scope.
    Spans are sorted by start (the longer first on a tie), so the last of
    those over a time is the innermost: ``devtrace.idle_gaps`` then names
    each gap by the innermost span over its middle."""
    import jax

    scopes = op_scopes(path)
    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace([], [], 0)
    names = set(span_names) | set(PROGRAM_SPANS) | {devtrace.WINDOW}
    devs = set()
    for plane in pd.planes:
        if plane.name.startswith(devtrace.DEVICE_PREFIX):
            dev = int(plane.name[len(devtrace.DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name != devtrace.OPS_LINE:
                    continue
                devs.add(dev)
                for ev in line.events:
                    tr.ops.append((dev, devtrace.op_name(ev.name),
                                   int(ev.start_ns),
                                   int(ev.start_ns + ev.duration_ns)))
                    tr.scopes.append(scopes.get(ev.name, ""))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        tr.spans.append((ev.name, int(ev.start_ns),
                                         int(ev.start_ns + ev.duration_ns)))
    tr.spans.sort(key=lambda x: (x[1], -x[2]))
    tr.devices = len(devs)
    return tr


def _self_ns(tr, intervals) -> dict[str, int]:
    """``devtrace.self_times`` of (device, label, start, end) intervals,
    each cut to the window first (which keeps their nesting)."""
    cut = []
    for d, label, s, e in intervals:
        s, e = devtrace._clip(tr, s, e)
        cut.append((d, label, s, max(e, s)))
    return devtrace.self_times(devtrace.Trace(cut, [], 1))


def host_ms_per_dispatch(tr) -> float | None:
    """The serving thread's own time per dispatch, in ms: the summed self
    time of the host-work spans inside the window over the number of
    dispatch spans that start in it."""
    w = devtrace.window(tr)
    if w is None:
        return None
    n = sum(1 for name, s, _ in tr.spans
            if name in DISPATCH and w[0] <= s < w[1])
    if not n:
        return None
    own = _self_ns(tr, [(0, name, s, e) for name, s, e in tr.spans
                        if name in PROGRAM_SPANS])
    return sum(t for name, t in own.items() if name in HOST_WORK) / n / 1e6


def scope_share(tr, scope: str) -> float | None:
    """Device self time, inside the window, of the ops whose op-name path
    holds ``scope`` as a whole component, over device busy time there, in
    %."""
    hit = [scope in sc.split("/") for sc in getattr(tr, "scopes", ())]
    if not any(hit):
        return None  # a program that names no such scope
    busy = devtrace.busy_s(tr)
    if busy <= 0:
        return None
    own = _self_ns(tr, [(o[0], "in" if h else "out", o[2], o[3])
                        for o, h in zip(tr.ops, hit)])
    return 100.0 * own.get("in", 0) / max(tr.devices, 1) / 1e9 / busy


def to_json(tr: Trace, start_ns: int, end_ns: int) -> dict:
    """``devtrace.to_json`` with each kept op's scope."""
    d = devtrace.to_json(tr, start_ns, end_ns)
    d["scopes"] = [sc for o, sc in zip(tr.ops, tr.scopes)
                   if o[3] > start_ns and o[2] < end_ns]
    return d


def from_json(d: dict) -> Trace:
    t = devtrace.from_json(d)
    return Trace(t.ops, t.spans, t.devices, list(d.get("scopes", [])))
