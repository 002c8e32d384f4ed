"""The JAX profiler around a sub-window, and the reduction of its trace.

``Profiler`` writes an ``.xplane.pb`` under a fresh directory of ``TMPDIR``,
with the Python tracer off (it would time the harness's own polling), and
brackets the traced window with a host span ``trace.window``.  ``load``
reads the file with ``jax.profiler.ProfileData`` into plain lists:

* device ops: every event of the ``XLA Ops`` line of each TPU plane, named
  by its HLO instruction (``paged_attention.9``).  Control-flow ops
  (``while``) appear there too and hold the ops they run, so busy time is a
  union of intervals and ``top_ops`` ranks self time;
* host spans: the harness's own ``TraceAnnotation`` spans, by name.

Host and device events share the trace's clock, so the window is the
``trace.window`` span and device time is clipped to it.  The reductions
work on those lists, so a test can feed them a small recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import re
import shutil
import tempfile

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "trace.window"


@dataclasses.dataclass
class Trace:
    ops: list  # [(device index, instruction name, start_ns, end_ns)]
    spans: list  # [(name, start_ns, end_ns)] harness spans on the host
    devices: int


class Profiler:
    def __init__(self):
        self.dir = None
        self.active = False
        self.path = None
        self._span = None

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW)
        self._span.__enter__()
        self.active = True

    def stop(self):
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        found = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
        self.path = found[0] if found else None

    def cleanup(self):
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def op_name(event_name: str) -> str:
    """``%paged_attention.9 = bf16[...] custom-call(...)`` -> ``paged_attention.9``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``paged_attention.9`` -> ``paged_attention`` (the HLO suffix off)."""
    return re.sub(r"(\.\d+)+$", "", name)


def load(path: str, span_names: tuple[str, ...] = ()) -> Trace:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops, spans, devs = [], [], set()
    names = set(span_names) | {WINDOW}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devs.add(dev)
                for ev in line.events:
                    ops.append((dev, op_name(ev.name), int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)))
    return Trace(ops, spans, len(devs))


def window(tr: Trace) -> tuple[int, int] | None:
    w = [(s, e) for n, s, e in tr.spans if n == WINDOW]
    return w[0] if w else None


def window_s(tr: Trace) -> float:
    w = window(tr)
    return (w[1] - w[0]) / 1e9 if w else 0.0


def _clip(tr: Trace, s: int, e: int) -> tuple[int, int]:
    w = window(tr)
    if w is None:
        return s, e
    return max(s, w[0]), min(e, w[1])


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace) -> float:
    """Seconds of the window in which some op ran on a device, averaged
    over the devices."""
    if not tr.devices:
        return 0.0
    total = 0
    for d in {o[0] for o in tr.ops}:
        total += sum(e - s for s, e in _union(
            _clip(tr, o[2], o[3]) for o in tr.ops if o[0] == d))
    return total / tr.devices / 1e9


def op_time_s(tr: Trace, kernels: tuple[str, ...]) -> float:
    """Summed device seconds, inside the window, of the ops whose
    instruction name (without its numeric suffix) is one of ``kernels``,
    averaged over the devices."""
    if not tr.devices:
        return 0.0
    want = set(kernels)
    t = 0
    for o in tr.ops:
        if base_name(o[1]) in want:
            s, e = _clip(tr, o[2], o[3])
            t += max(e - s, 0)
    return t / tr.devices / 1e9


def self_times(tr: Trace) -> dict[str, int]:
    """Self time (ns) per instruction base name: an op's duration less the
    ops nested inside it on the same device."""
    out: dict[str, int] = {}
    for d in {o[0] for o in tr.ops}:
        stack: list[list] = []  # [end, name, self]
        for _, name, s, e in sorted((o for o in tr.ops if o[0] == d),
                                    key=lambda o: (o[2], -o[3])):
            while stack and stack[-1][0] <= s:
                _, n, st = stack.pop()
                out[n] = out.get(n, 0) + st
            if stack:
                stack[-1][2] -= min(e, stack[-1][0]) - s
            stack.append([e, base_name(name), e - s])
        for _, n, st in stack:
            out[n] = out.get(n, 0) + st
    return out


def top_ops(tr: Trace, k: int = 10) -> list[list]:
    best = sorted(self_times(tr).items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / max(tr.devices, 1) / 1e9] for n, t in best]


def idle_gaps(tr: Trace, k: int = 10) -> list[list]:
    """The longest gaps between device ops on the first device, inside the
    window, each named by the harness span that covered its middle."""
    d0 = min((o[0] for o in tr.ops), default=None)
    if d0 is None:
        return []
    w = window(tr)
    busy = _union(_clip(tr, o[2], o[3]) for o in tr.ops if o[0] == d0)
    edges = ([(w[0], w[0])] if w else []) + busy + ([(w[1], w[1])] if w else [])
    gaps = [(edges[i + 1][0] - edges[i][1], edges[i][1], edges[i + 1][0])
            for i in range(len(edges) - 1)]
    gaps = sorted((g for g in gaps if g[0] > 0), key=lambda g: -g[0])
    out = []
    for g, s, e in gaps[:k]:
        mid = (s + e) // 2
        names = [n for n, a, b in tr.spans if a <= mid <= b and n != WINDOW]
        out.append([names[-1] if names else "outside harness spans", g / 1e9])
    return out


def to_json(tr: Trace, start_ns: int, end_ns: int) -> dict:
    """The part of a trace between two times as plain JSON (ops cut to it),
    with a window span over it: the form tests keep a recorded trace in."""
    ops = [[o[0], o[1], max(o[2], start_ns), min(o[3], end_ns)]
           for o in tr.ops if o[3] > start_ns and o[2] < end_ns]
    spans = [[n, max(s, start_ns), min(e, end_ns)] for n, s, e in tr.spans
             if n != WINDOW and e > start_ns and s < end_ns]
    return {"ops": ops, "spans": spans + [[WINDOW, start_ns, end_ns]],
            "devices": tr.devices}


def from_json(d: dict) -> Trace:
    return Trace([tuple(o) for o in d["ops"]], [tuple(s) for s in d["spans"]],
                 d["devices"])
