"""One dispatch point for the attention-kernel family.

Every attention call site (dense prefill, paged decode, ragged span —
spec verify rides the span variant) asks :func:`resolve` which backend
to run.  The answer is a :class:`KernelDecision`.  Off TPU an unsupported
shape or platform degrades to the XLA path with a reason string.  On TPU
in ``auto`` mode it is a :class:`KernelFallbackError` naming the reason: a
chip run must not serve without its kernels unnoticed (``kernel_mode=xla``
asks for the XLA path on purpose).

Modes (``cfg.kernel_mode``, overridable via ``REPRO_KERNEL_MODE``):

* ``auto`` (default) — Pallas wherever shape/dtype allow **on TPU**;
  off-TPU the Pallas runtime is interpret-mode emulation (an order of
  magnitude slower than XLA), so auto falls back to XLA there.
* ``pallas`` — force the Pallas kernels wherever supported, interpret
  mode off-TPU (what the CI kernel job runs); unsupported shapes still
  fall back to XLA.
* ``xla`` — always the gather/SDPA jnp path (the pre-refactor default).

Decisions are observable: engines log per-variant dispatch counts
(``stats["kernel_dispatch"]``) and emit EV_KERNEL_VARIANT into the trace
with the ``KERNEL_VARIANT_IDS`` value of what actually ran.
"""
from __future__ import annotations

import dataclasses
import os

from repro.kernels.attention import autotune

MODES = ("auto", "pallas", "xla")
VARIANTS = ("dense", "paged_decode", "paged_span")
MODE_ENV = "REPRO_KERNEL_MODE"

# trace-event values for EV_KERNEL_VARIANT (0 is reserved: "no dispatch")
KERNEL_VARIANT_IDS = {
    "dense:xla": 1,
    "dense:pallas": 2,
    "paged_decode:xla": 3,
    "paged_decode:pallas": 4,
    "paged_span:xla": 5,
    "paged_span:pallas": 6,
}

_SUPPORTED_DTYPES = ("float32", "bfloat16")

# re-exported: the observer also receives EV_KERNEL_VARIANT from engines
set_observer = autotune.set_observer
notify = autotune.notify


class KernelFallbackError(ValueError):
    """``auto`` mode on TPU would have run an attention variant on XLA."""


@dataclasses.dataclass(frozen=True)
class KernelDecision:
    variant: str   # dense | paged_decode | paged_span
    backend: str   # pallas | xla
    params: dict = dataclasses.field(default_factory=dict)
    reason: str = ""

    @property
    def tag(self) -> str:
        return f"{self.variant}:{self.backend}"

    @property
    def event_value(self) -> int:
        return KERNEL_VARIANT_IDS[self.tag]


def mode_from(cfg) -> str:
    """The effective kernel mode for a config: env override first, then
    ``cfg.kernel_mode``, then the deprecated per-family flags."""
    env = os.environ.get(MODE_ENV, "")
    if env:
        if env not in MODES:
            raise ValueError(f"{MODE_ENV}={env!r}: expected one of {MODES}")
        return env
    mode = getattr(cfg, "kernel_mode", None)
    if mode is not None:
        return mode
    if getattr(cfg, "use_paged_kernel", False) or getattr(cfg, "use_flash_kernel", False):
        return "pallas"
    return "auto"


def _platform() -> str:
    import jax

    return jax.default_backend()


def resolve(mode: str, variant: str, *, head_dim: int, kv_heads: int,
            dtype: str, window: int | None = None, block_size: int = 0,
            supported: bool = True, why: str = "",
            platform: str | None = None, measure=None,
            kv_dtype: str = "fp16") -> KernelDecision:
    """Decide pallas-vs-XLA for one attention call site.

    ``supported``/``why`` carry call-site constraints the dispatcher cannot
    see (head-dim sharding, non-array positions, ...).  ``platform`` is
    injectable so the TPU dispatch table is testable off-TPU.  Pallas
    decisions carry tuned tiling parameters from the autotune layer;
    ``kv_dtype`` is the KV *storage* dtype (the paged variants fuse dequant,
    so int8 and fp16 pools tune — and cache — separately).
    """
    if mode not in MODES:
        raise ValueError(f"kernel_mode {mode!r}: expected one of {MODES}")
    if variant not in VARIANTS:
        raise ValueError(f"kernel variant {variant!r}: expected one of {VARIANTS}")
    if mode == "xla":
        return KernelDecision(variant, "xla", reason="mode=xla")
    plat = platform or _platform()
    if not supported:
        unfit = why or "unsupported call site"
    elif str(dtype) not in _SUPPORTED_DTYPES:
        unfit = f"dtype {dtype} unsupported"
    elif head_dim % 8:
        unfit = f"head_dim {head_dim} not lane-tileable"
    else:
        unfit = ""
    if unfit:
        if mode == "auto" and plat == "tpu":
            raise KernelFallbackError(
                f"{variant} cannot run its Pallas kernel on TPU ({unfit}); "
                f"set kernel_mode='xla' to serve it on XLA deliberately")
        return KernelDecision(variant, "xla", reason=unfit)
    if mode == "auto" and plat != "tpu":
        # interpret-mode Pallas is emulation, not a fast path
        return KernelDecision(variant, "xla", reason=f"auto: {plat} has no Mosaic")
    params = autotune.params_for(
        variant, head_dim=head_dim, kv_heads=kv_heads, block_size=block_size,
        window=window, dtype=str(dtype), platform=plat, measure=measure,
        kv_dtype=kv_dtype,
    )
    reason = "auto: tpu" if mode == "auto" else "mode=pallas"
    return KernelDecision(variant, "pallas", params=params, reason=reason)


def engine_plan(cfg, *, block_size: int = 0, hd_shards: int = 1,
                platform: str | None = None) -> dict[str, KernelDecision]:
    """Resolve every variant once for an engine's config (logged at init
    and used for per-dispatch accounting).  ``hd_shards > 1`` splits
    head_dim across devices, which no Pallas variant supports."""
    mode = mode_from(cfg)
    shard_ok = hd_shards == 1
    why = "" if shard_ok else f"head_dim sharded {hd_shards}-way"
    return {
        variant: resolve(
            mode, variant, head_dim=cfg.head_dim, kv_heads=cfg.num_kv_heads,
            dtype=cfg.dtype, window=cfg.attention_window,
            block_size=block_size, supported=shard_ok, why=why,
            platform=platform,
            kv_dtype=getattr(cfg, "kv_dtype", "fp16"),
        )
        for variant in VARIANTS
    }
