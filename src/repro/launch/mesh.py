"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.parallel.axes import (AxisRules, multi_pod_rules, pure_fsdp_rules,
                                 single_pod_rules)


def auto_axis_types_kw(n_axes: int) -> Dict[str, Tuple]:
    """``{"axis_types": (Auto,) * n}``: every mesh axis Auto."""
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_auto_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         **auto_axis_types_kw(len(axes)))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def rules_for(mesh: Mesh, layout: str = "tp") -> AxisRules:
    """layout: "tp" (TP over model + FSDP over data, the baseline) or "fsdp"
    (pure 256-way ZeRO-3, single-pod only — multi-pod falls back to tp since
    global_batch 256 cannot split 512 ways)."""
    if "pod" in mesh.axis_names:
        return multi_pod_rules()
    if layout == "fsdp":
        return pure_fsdp_rules()
    return single_pod_rules()


def make_smoke_mesh(n_devices: int = 1) -> Mesh:
    """Tiny mesh over however many real devices exist (tests)."""
    devs = jax.devices()[:n_devices]
    return Mesh(
        __import__("numpy").array(devs).reshape(1, len(devs)),
        ("data", "model"),
        **auto_axis_types_kw(2),
    )
