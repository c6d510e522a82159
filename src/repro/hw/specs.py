"""TPU hardware specifications.

The paper's GPU hardware query system (Xe-Forge IV-E) reads device properties at
runtime (EU count, SLM capacity, GRF modes, ...). On a TPU the runtime reports
only ``device_kind``, so the spec table is the detection path:
``spec_for_device`` maps the attached chip's kind to a :class:`TPUSpec` and
refuses a kind it does not know; ``get_spec`` maps a generation name (the
optimization target a ``ForgeConfig`` names) to one.

All constants are per-chip (one TensorCore exposed per v5e chip). The roofline
constants used by the assignment are the v5e ones: 197 bf16 TFLOP/s, 819 GB/s
HBM, ~50 GB/s per ICI link.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    """Per-chip TPU hardware description (planning model, not a simulator)."""

    name: str
    # Compute.
    peak_flops_bf16: float  # FLOP/s with bf16 inputs / f32 accumulation (MXU)
    peak_flops_f32: float   # FLOP/s at f32 (MXU passes / VPU)
    mxu_shape: Tuple[int, int] = (128, 128)  # systolic array tile
    vpu_lanes: int = 128     # vector lane width (last dim)
    sublanes: int = 8        # second-to-last dim tile at f32
    # Memory hierarchy.
    hbm_bytes: int = 16 * 2**30
    hbm_bw: float = 819e9            # bytes/s
    vmem_bytes: int = 64 * 2**20     # usable VMEM planning budget (assumption; cf. DESIGN.md)
    vmem_bw: float = 20e12           # effective VMEM bandwidth (order-of-magnitude planning figure)
    smem_bytes: int = 1 * 2**20      # scalar memory (SMEM) budget for scalar prefetch args
    # Interconnect.
    ici_link_bw: float = 50e9        # bytes/s per link (assignment constant)
    ici_links: int = 4               # 2D torus on v5e: 4 links/chip
    # Misc planning knobs.
    launch_overhead_s: float = 2e-6  # fixed per-kernel launch/pipeline-fill overhead

    # ---- derived helpers -------------------------------------------------
    def peak_flops(self, dtype: str) -> float:
        if dtype in ("bf16", "bfloat16", "f16", "float16", "fp16"):
            return self.peak_flops_bf16
        if dtype in ("int8", "i8", "fp8"):
            # v5e int8: 394 TOPS (2x bf16)
            return self.peak_flops_bf16 * 2
        if dtype in ("float64", "f64"):
            # no native f64: XLA software emulation
            return self.peak_flops_f32 / 8
        return self.peak_flops_f32

    def min_tile(self, dtype: str) -> Tuple[int, int]:
        """Native (sublane, lane) tile for a dtype: (8,128) f32, (16,128) bf16, (32,128) int8."""
        itemsize = dtype_itemsize(dtype)
        packing = max(1, 4 // itemsize)
        return (self.sublanes * packing, self.vpu_lanes)


def dtype_itemsize(dtype: str) -> int:
    d = str(dtype)
    if d in ("float64", "f64", "int64", "i64"):
        return 8
    if d in ("float32", "f32", "int32", "i32", "uint32"):
        return 4
    if d in ("bfloat16", "bf16", "float16", "f16", "fp16", "int16"):
        return 2
    if d in ("int8", "i8", "uint8", "fp8", "float8_e4m3fn", "float8_e5m2"):
        return 1
    raise ValueError(f"unknown dtype {dtype!r}")


# TPU v5e ("the assignment target"): 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
TPU_V5E = TPUSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_f32=197e12 / 4,  # f32 matmul runs the MXU at ~1/4 rate
)

# TPU v4 for comparison experiments.
TPU_V4 = TPUSpec(
    name="tpu_v4",
    peak_flops_bf16=275e12,
    peak_flops_f32=275e12 / 4,
    hbm_bytes=32 * 2**30,
    hbm_bw=1228e9,
    vmem_bytes=128 * 2**20,
    ici_link_bw=50e9,
    ici_links=6,  # 3D torus
)

# TPU v5p.
TPU_V5P = TPUSpec(
    name="tpu_v5p",
    peak_flops_bf16=459e12,
    peak_flops_f32=459e12 / 4,
    hbm_bytes=95 * 2**30,
    hbm_bw=2765e9,
    vmem_bytes=128 * 2**20,
    ici_link_bw=100e9,
    ici_links=6,
)

_SPECS: Dict[str, TPUSpec] = {s.name: s for s in (TPU_V5E, TPU_V4, TPU_V5P)}
_SPECS.update({"v5e": TPU_V5E, "v4": TPU_V4, "v5p": TPU_V5P})


# ``jax.Device.device_kind`` -> spec. Peaks: Google Cloud TPU documentation
# ("TPU v5e", "TPU v4", "TPU v5p" system architecture pages).
_KINDS: Dict[str, TPUSpec] = {"TPU v5 lite": TPU_V5E, "TPU v4": TPU_V4,
                              "TPU v5": TPU_V5P}


def spec_for_kind(kind: str) -> TPUSpec:
    try:
        return _KINDS[kind]
    except KeyError:
        raise KeyError(f"no spec for device_kind {kind!r}; known: "
                       f"{sorted(_KINDS)}") from None


def spec_for_device(device=None) -> TPUSpec:
    """The spec of ``device`` (default: the first JAX device), which must be
    a TPU of a known kind — never a default."""
    import jax
    device = device if device is not None else jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(f"no TPU attached: the first device is "
                           f"{device.platform!r} ({device.device_kind})")
    return spec_for_kind(device.device_kind)


def get_spec(name: str = "tpu_v5e") -> TPUSpec:
    """Hardware 'detection': resolve a generation name to its spec."""
    try:
        return _SPECS[name]
    except KeyError:
        raise KeyError(f"unknown TPU generation {name!r}; known: {sorted(_SPECS)}")
