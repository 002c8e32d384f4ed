"""A small stand-in of a cell for the CPU tests: the configuration at its
architecture's small sizes (``tiny``), with short traffic.  Built from the
real files, so the tests run the real harness code on other sizes."""
from __future__ import annotations

import copy

import cellspec

PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_s": 1e11,
         "hbm_bytes": 1e9}


def config(name: str = "granite-8b-l18", dtype: str = "float32") -> dict:
    c = copy.deepcopy(cellspec.config(name))
    c.update(cellspec.arch(c.get("reference")).tiny(c, dtype))
    return c


def mix(name: str) -> dict:
    m = copy.deepcopy(cellspec.mix(name))
    m["engine"].update(num_slots=4, max_len=256, num_blocks=80,
                       max_step_tokens=36, chunk_size=16, chunk_rows=2)
    for key in ("prompt", "output", "message", "history_reply"):
        if key in m:
            m[key].update(min=8, max=48)
            if "median" in m[key]:
                m[key]["median"] = 24
    if m["kind"] == "closed":
        m["context"] = 96
        m["requests"] = 16
    else:
        m.update(rate_rps=8.0, lead_s=0.5, tail_s=10.0)
    if m["kind"] == "sessions":
        m.update(sessions=6, system_prompt=32)
    m["check"] = {"tokens": 48, "max_total": 1024, "max_seqs": 3}
    return m


def files(workload: str, dtype: str = "float32", limit: float = 0.05) -> dict:
    spec = cellspec.bench()
    cell = cellspec.cell(workload, spec)
    c = config(cell["config"], dtype)
    c["check"] = {"max_logit_gap": limit}
    return {"bench": spec, "config": c, "mix": mix(cell["traffic"]),
            "peaks": PEAKS}
