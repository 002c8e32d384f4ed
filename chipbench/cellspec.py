"""Find a cell's files by the names in ``BENCHMARK.json``.

configs/<config>.json   the model, its cut, its architecture, the weight map,
                        the check limit
arch/<reference>.py     the architecture a configuration names (``arch``)
traffic/<traffic>.json  the mix: load parameters and deployment settings
metrics/<metric>.py     one reader per metric: ``read(rec) -> float | None``
peaks.json              the chip's published peaks, by ``device_kind``
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
ARCH = HERE / "arch"


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(name: str, spec: dict | None = None) -> dict:
    spec = spec or bench()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    return cells[name]


def config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def arch(name: str | None):
    """The architecture module ``arch/<name>.py`` that a configuration
    names under ``"reference"``.  It gives the shared code what depends on
    the layers' mathematics (``arch/dense_gqa.py`` is the first):

    - ``KEYS``: the configuration keys its reference reads;
    - ``IDS``: {tensor name: draw id} of its layers' tensors, by the rule
      in ``seedweights``;
    - ``shapes(c)``: the shape of one draw of every tensor it draws, the
      global ``embed``, ``lm_head`` and ``final_norm`` included;
    - ``experts(c)``: {tensor name: experts} of the tensors drawn once per
      published expert index;
    - ``kind(c, i)``: layer i's kind, hashable; ``tensors(c, kind)``: the
      tensors such a layer draws; ``forward(w, x, pos, kind, *, c, fp8,
      qblk)``: its float32 forward of x [B, L, d] at positions pos [L],
      matrix products through ``refmodel._mm``.  One compile per kind;
    - ``program(c)``: {field of the program's config: the value it must
      have}; ``tiny(c, dtype)``: the keys that cut the configuration to a
      small CPU size, its ``program`` block among them;
    - the work counts behind ``workcount``'s public functions, with their
      signatures.
    """
    known = sorted(p.stem for p in ARCH.glob("*.py"))
    if name not in known:
        raise SystemExit(f"the configuration's \"reference\" names "
                         f"architecture {name!r}; known: {known}")
    sp = importlib.util.spec_from_file_location(f"chipbench_arch_{name}",
                                                ARCH / f"{name}.py")
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


def metrics_for(workload: str, trace: bool, spec: dict | None = None) -> list[dict]:
    """The metrics a run of this cell reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    spec = spec or bench()
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    sp = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read
