"""Roofline analysis from the compiled dry-run artifact (no real hardware).

Three terms per (arch x shape x mesh), all in seconds (TPU v5e-like
constants from the task spec):

  compute    = HLO_FLOPs / (chips x 197 TFLOP/s bf16)
  memory     = HLO_bytes / (chips x 819 GB/s HBM)
  collective = collective_bytes / (chips x 50 GB/s link)

``compiled.cost_analysis()`` on an SPMD module reports per-partition numbers
(verified empirically — see DESIGN.md), so the per-chip terms divide by the
single-chip peak directly; the table reports the equivalent global numbers.
collective_bytes sums operand sizes of every collective parsed out of
``compiled.as_text()`` (spec formula); a ring-aware wire-bytes estimate is
reported alongside.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import numpy as np

from repro.core.hlo_comm import collective_summary, parse_collectives

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
LINK_BW = 50e9  # bytes/s per ICI link
HBM_BYTES = 16 * 1024**3  # per chip


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    # per-device quantities from the compiled module
    flops_dev: float
    bytes_dev: float
    coll_operand_bytes_dev: float
    coll_wire_bytes_dev: float
    coll_count: int
    coll_by_kind: dict
    temp_bytes_dev: float
    arg_bytes_dev: float
    out_bytes_dev: float
    # model-level accounting
    model_flops_global: float
    # XLA's own cost_analysis (scan bodies counted once — for cross-checking)
    xla_flops_dev: float = 0.0
    xla_bytes_dev: float = 0.0

    # ---- the three roofline terms (seconds) ----
    @property
    def compute_s(self) -> float:
        return self.flops_dev / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_dev / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll_operand_bytes_dev / LINK_BW

    @property
    def collective_wire_s(self) -> float:
        return self.coll_wire_bytes_dev / LINK_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs x chips) — remat/redundancy waste."""
        tot = self.flops_dev * self.chips
        return self.model_flops_global / tot if tot else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Model-useful compute time / achievable step time bound.  This is
        the MFU-at-roofline figure reported in EXPERIMENTS.md section Perf."""
        ideal = self.model_flops_global / (self.chips * PEAK_FLOPS)
        return ideal / self.bound_s if self.bound_s else 0.0

    @property
    def fits_hbm(self) -> bool:
        return (self.temp_bytes_dev + self.arg_bytes_dev) <= HBM_BYTES

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_dev": self.flops_dev, "bytes_dev": self.bytes_dev,
            "coll_operand_bytes_dev": self.coll_operand_bytes_dev,
            "coll_wire_bytes_dev": self.coll_wire_bytes_dev,
            "coll_count": self.coll_count,
            "coll_by_kind": self.coll_by_kind,
            "temp_bytes_dev": self.temp_bytes_dev,
            "arg_bytes_dev": self.arg_bytes_dev,
            "out_bytes_dev": self.out_bytes_dev,
            "model_flops_global": self.model_flops_global,
            "xla_flops_dev": self.xla_flops_dev,
            "xla_bytes_dev": self.xla_bytes_dev,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "collective_wire_s": self.collective_wire_s,
            "dominant": self.dominant, "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
            "fits_hbm": self.fits_hbm,
        }


def model_flops(model, shape) -> float:
    """MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D (inference), where
    N_active counts matmul parameters with MoE experts scaled to the routed
    fraction and embedding tables excluded (the logits matmul is counted
    explicitly)."""
    from repro.models.params import is_decl
    from repro.sharding.partition import padded_vocab

    cfg = model.cfg
    paths = jax.tree_util.tree_flatten_with_path(model._decl, is_leaf=is_decl)[0]
    n_active = 0.0
    for path, d in paths:
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if any(k == "embedding" for k in keys):
            continue  # gather, not matmul
        n = float(np.prod(d.shape))
        if "experts" in d.axes and cfg.num_experts:
            # only the routed top-k experts are active per token
            e_dim = d.shape[d.axes.index("experts")]
            n = n / e_dim * min(cfg.experts_per_token, cfg.num_experts)
        n_active += n
    if cfg.tie_embeddings:
        n_active += cfg.d_model * padded_vocab(cfg.vocab_size)

    if shape.kind == "train":
        factor, tokens = 6.0, shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        factor, tokens = 2.0, shape.global_batch * shape.seq_len
    else:  # decode: one token per sequence
        factor, tokens = 2.0, shape.global_batch
    return factor * n_active * tokens


def analyze_compiled(compiled, *, arch: str, shape, mesh, model_flops_global: float):
    """Derive per-device counters from the compiled module.

    The primary counters come from ``repro.core.hlo_cost`` (while-loop
    trip-count aware — XLA's own cost_analysis counts scan bodies ONCE and
    under-reports layer-stacked models by ~num_layers); XLA's numbers are
    kept alongside for cross-checking.
    """
    from repro.core.hlo_cost import analyze_hlo
    from repro.launch.mesh import mesh_desc

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    hc = analyze_hlo(text, total_devices=mesh.size)
    cs = collective_summary(hc.collectives)

    rl = Roofline(
        arch=arch, shape=shape.name, mesh=mesh_desc(mesh), chips=mesh.size,
        flops_dev=float(hc.flops),
        bytes_dev=float(hc.bytes_accessed),
        coll_operand_bytes_dev=float(hc.coll_operand_bytes),
        coll_wire_bytes_dev=float(hc.coll_wire_bytes),
        coll_count=int(cs["count"]),
        coll_by_kind={k: v["count"] for k, v in cs["by_kind"].items()},
        temp_bytes_dev=float(getattr(ma, "temp_size_in_bytes", 0) or 0),
        arg_bytes_dev=float(getattr(ma, "argument_size_in_bytes", 0) or 0),
        out_bytes_dev=float(getattr(ma, "output_size_in_bytes", 0) or 0),
        model_flops_global=model_flops_global,
    )
    rl.xla_flops_dev = float(ca.get("flops", 0.0))
    rl.xla_bytes_dev = float(ca.get("bytes accessed", 0.0))
    return rl


def format_table(rows: list[dict]) -> str:
    cols = ["arch", "shape", "mesh", "dominant", "compute_s", "memory_s",
            "collective_s", "useful_ratio", "roofline_fraction", "fits_hbm"]
    widths = {c: max(len(c), *(len(_fmt(r.get(c))) for r in rows)) for c in cols} if rows else {}
    head = "  ".join(c.ljust(widths[c]) for c in cols)
    lines = [head, "-" * len(head)]
    for r in rows:
        lines.append("  ".join(_fmt(r.get(c)).ljust(widths[c]) for c in cols))
    return "\n".join(lines)


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.3e}" if (abs(v) < 1e-3 or abs(v) >= 1e4) else f"{v:.4f}"
    return str(v)


def save_rows(rows: list[dict], path: str):
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
