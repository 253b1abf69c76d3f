"""Device peaks, the device check, and peak memory.

Peaks are per chip, keyed by ``device_kind`` as JAX reports it.  A device
kind that is not in the table is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

from typing import Dict, List

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2**30},
}


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def peaks(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[kind]


def check(jax, chips: int, allow_cpu: bool = False) -> List:
    """The first ``chips`` devices; raises :class:`NoDevice` unless they are
    TPUs (or ``allow_cpu``, for the CPU rehearsal, which reports no device
    metric)."""
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoDevice(f"JAX found no TPU (first device: {devs[0].platform})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX has {len(devs)}")
    return devs[:chips]


def describe(devs: List) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs: List) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where the backend
    keeps no statistics)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
