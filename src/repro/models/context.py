"""MeshCtx: everything a model needs to know about the device mesh."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.params import DEFAULT_RULES


def make_mesh(shape, axis_names, devices=None):
    """jax.make_mesh with every axis in Auto sharding mode."""
    kw = {} if devices is None else {"devices": devices}
    auto = (jax.sharding.AxisType.Auto,) * len(shape)
    return jax.make_mesh(shape, axis_names, axis_types=auto, **kw)


@dataclass
class MeshCtx:
    mesh: Mesh
    rules: Dict[str, Any]

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.mesh.axis_names if a != "model")

    @property
    def model_axis(self) -> Optional[str]:
        return "model" if "model" in self.mesh.axis_names else None

    def batch_spec(self, *trailing) -> P:
        return P(self.batch_axes, *trailing)

    def constraint(self, x, spec: P):
        """with_sharding_constraint that replicates any non-divisible dim."""
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        parts = []
        for dim, p in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
            if p is None:
                parts.append(None)
                continue
            axes = tuple(a for a in (p if isinstance(p, (tuple, list)) else (p,))
                         if a in sizes)
            n = 1
            for a in axes:
                n *= sizes[a]
            if axes and n > 1 and dim % n == 0:
                parts.append(axes if len(axes) > 1 else axes[0])
            else:
                parts.append(None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*parts)))

    def dp_size(self) -> int:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        n = 1
        for a in self.batch_axes:
            n *= sizes[a]
        return n

    def tp_size(self) -> int:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return sizes.get("model", 1)


def make_rules(cfg) -> Dict[str, Any]:
    rules = dict(DEFAULT_RULES)
    rules["fsdp"] = ("data",) if getattr(cfg, "fsdp", False) else None
    return rules


def single_device_ctx(cfg=None) -> MeshCtx:
    """1x1 mesh for smoke tests — same code path as production."""
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    return MeshCtx(mesh=mesh, rules=make_rules(cfg) if cfg is not None else dict(DEFAULT_RULES))
