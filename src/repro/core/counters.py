"""Counter backends — the PAPI analogue (paper section 3).

PAPI does not exist inside an XLA program; two replacement sources:

  * :func:`rusage_counters` — host OS counters (RSS, user/sys time, faults);
  * :class:`StepCounters`   — deterministic per-step "hardware counters"
    derived from the compiled step's ``cost_analysis()`` (HLO FLOPs, bytes)
    and the HLO collective summary (collective bytes).  Emitted as Paraver
    counter events at each step boundary, they give exactly the
    counters-per-region view Extrae gets from PAPI.
"""
from __future__ import annotations

import resource

from repro.core import events as ev


def rusage_counters() -> list[tuple[int, int]]:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return [
        (ev.EV_CTR_RSS, int(ru.ru_maxrss)),
        (ev.EV_CTR_UTIME, int(ru.ru_utime * 1e6)),
        (ev.EV_CTR_STIME, int(ru.ru_stime * 1e6)),
        (ev.EV_CTR_MINFLT, int(ru.ru_minflt)),
    ]


class StepCounters:
    """Per-step counter emission, configured once from a compiled artifact."""

    def __init__(self, flops_per_step: int = 0, bytes_per_step: int = 0,
                 coll_bytes_per_step: int = 0):
        self.flops = int(flops_per_step)
        self.bytes = int(bytes_per_step)
        self.coll = int(coll_bytes_per_step)

    @classmethod
    def from_compiled(cls, compiled, coll_bytes: int = 0):
        ca = compiled.cost_analysis() or {}
        return cls(
            flops_per_step=int(ca.get("flops", 0)),
            bytes_per_step=int(ca.get("bytes accessed", 0)),
            coll_bytes_per_step=int(coll_bytes),
        )

    def emit(self, tracer, *, include_rusage: bool = True):
        pairs = [
            (ev.EV_CTR_FLOPS, self.flops),
            (ev.EV_CTR_BYTES, self.bytes),
            (ev.EV_CTR_COLL_BYTES, self.coll),
        ]
        if include_rusage:
            pairs += rusage_counters()
        tracer.emit_many(pairs)
