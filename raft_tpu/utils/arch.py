"""Accelerator-generation dispatch + per-generation roofline peaks.

(ref: cpp/include/raft/util/arch.cuh — runtime SM-architecture ranges used
to pick kernel variants per GPU generation. The TPU equivalent keys off
``device_kind`` — v4/v5e/v5p/v6 … — so Pallas kernels can pick tile sizes
per generation.)

This module also carries the hardware half of the roofline model
(Williams et al., CACM 2009): :class:`ChipSpec` peak matmul FLOP/s and
HBM bandwidth per TPU generation, consumed by
:mod:`raft_tpu.observability.costmodel` to turn XLA ``cost_analysis``
FLOPs/bytes into %-of-roofline utilization. A CPU entry exists so the
full roofline path runs (deterministically) on the tier-1 CPU suite.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

import jax


def device_kind(device: Optional[jax.Device] = None) -> str:
    dev = device or jax.devices()[0]
    return getattr(dev, "device_kind", "cpu")


def tpu_generation(device: Optional[jax.Device] = None) -> int:
    """TPU generation number (4, 5, 6, ...); 0 for non-TPU platforms."""
    kind = device_kind(device).lower()
    m = re.search(r"v(\d+)", kind)
    return int(m.group(1)) if m else 0


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip roofline peaks (public spec sheets, per chip — not per
    core/pod). ``peak_flops`` is the dense-matmul MXU peak at the native
    accumulation precision (bf16 inputs, f32 accumulate);
    ``peak_flops_f32`` is the ≈3-pass hi/lo-split f32 matmul rate (the
    split costs 3 MXU passes plus rounding overhead — an estimate, used
    only to place the f32 ridge point, never reported as a measurement).
    ``hbm_bw`` is bytes/s, ``hbm_bytes`` total device HBM.

    ``ici_bw`` is the per-chip AGGREGATE one-way inter-chip-interconnect
    bandwidth in bytes/s (all links; public spec-sheet Gbps ÷ 8) — the
    denominator of every busbw fraction the multichip artifacts record,
    and the wire term of :func:`raft_tpu.observability.costmodel.
    ici_time_model`. ``ici_latency`` is a per-collective-round latency
    estimate in seconds (link + XLA launch), the fixed cost that makes
    a log₂(p) tournament lose to one allgather at small payloads."""

    name: str
    peak_flops: float       # FLOP/s, bf16 matmul (MXU)
    peak_flops_f32: float   # FLOP/s, f32-grade matmul (split-pass estimate)
    hbm_bw: float           # bytes/s
    hbm_bytes: float        # bytes
    ici_bw: float = 0.0     # bytes/s, aggregate one-way per chip
    ici_latency: float = 1e-6   # seconds per collective round (estimate)

    @property
    def ridge(self) -> float:
        """Arithmetic intensity (FLOP/byte) where the bf16 roofline goes
        flat: below it a kernel is memory-bound, above compute-bound."""
        return self.peak_flops / self.hbm_bw

    @property
    def ridge_f32(self) -> float:
        return self.peak_flops_f32 / self.hbm_bw


# Public per-chip peaks. Keyed by (generation, variant); variant "" means
# the generation's only (or default) chip. ICI aggregates from the public
# spec sheets: v3 4×162.5 Gbps links ≈ 650 Gbps, v4 2400 Gbps (6 links,
# 3-D torus), v5e 1600 Gbps (4×400), v5p 4800 Gbps (6×800), v6e
# 3584 Gbps (4×896) — ÷8 for bytes/s.
_T = 1e12
_G = 1e9
TPU_SPECS = {
    (3, ""): ChipSpec("tpu v3", 123 * _T, 123 * _T / 3, 900 * _G, 32 * _G,
                      ici_bw=81 * _G),
    (4, ""): ChipSpec("tpu v4", 275 * _T, 275 * _T / 3, 1228 * _G, 32 * _G,
                      ici_bw=300 * _G),
    (5, "e"): ChipSpec("tpu v5e", 197 * _T, 197 * _T / 3, 819 * _G, 16 * _G,
                       ici_bw=200 * _G),
    (5, "p"): ChipSpec("tpu v5p", 459 * _T, 459 * _T / 3, 2765 * _G, 95 * _G,
                       ici_bw=600 * _G),
    (6, "e"): ChipSpec("tpu v6e", 918 * _T, 918 * _T / 3, 1640 * _G, 32 * _G,
                       ici_bw=448 * _G),
}

# The CPU fallback the tier-1 suite rooflines against: order-of-magnitude
# single-socket numbers, chosen so the ridge sits at 8 FLOP/byte — a GEMM
# (AI ~ d/6 for square operands ≥ 128) classifies compute-bound and an
# SpMV/elementwise pass (AI < 1) memory-bound, same as on real TPU specs.
# The synthetic "ICI" (the virtual-device memcpy fabric) is priced well
# below hbm_bw so merge-strategy ranking exercises the same wire-vs-
# select trade-off the TPU specs present.
CPU_SPEC = ChipSpec("cpu (synthetic roofline)", 200 * _G, 100 * _G,
                    25 * _G, 64 * _G, ici_bw=5 * _G, ici_latency=2e-6)


def chip_spec(device: Optional[jax.Device] = None) -> ChipSpec:
    """Roofline peaks for ``device`` (default: the first device).

    TPU kinds resolve by generation + lite/p variant (``TPU v5 lite`` /
    ``TPU v5e`` → v5e; ``TPU v5p`` → v5p). A TPU kind that is not in
    :data:`TPU_SPECS` is an error: a roofline share against another
    chip's peaks would be a wrong number, not an approximate one.
    Non-TPU platforms get :data:`CPU_SPEC` — synthetic, but fixed, so
    tier-1 tests exercise the full classification path."""
    kind = device_kind(device).lower()
    gen = tpu_generation(device)
    if gen == 0 and "tpu" not in kind:
        return CPU_SPEC
    variant = ""
    if "lite" in kind or re.search(r"v\d+\s*e", kind):
        variant = "e"
    elif re.search(r"v\d+\s*p", kind):
        variant = "p"
    spec = TPU_SPECS.get((gen, variant)) or TPU_SPECS.get((gen, ""))
    if spec is None:
        raise ValueError(f"chip_spec: TPU kind {device_kind(device)!r} "
                         f"has no entry in TPU_SPECS")
    return spec


class ArchRange:
    """Half-open generation range for kernel dispatch.
    (ref: util/arch.cuh ``SM_range``)"""

    def __init__(self, min_gen: int, max_gen: int = 1 << 30):
        self.min_gen = min_gen
        self.max_gen = max_gen

    def contains(self, gen: Optional[int] = None) -> bool:
        g = tpu_generation() if gen is None else gen
        return self.min_gen <= g < self.max_gen
