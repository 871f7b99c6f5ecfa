"""Mesh construction for the production topologies.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): 16×16 = 256 chips per pod (`("data","model")`), or
2×16×16 = 512 chips across two pods (`("pod","data","model")`).

``rules_for`` builds the logical-sharding rules for an (arch, mesh) pair:
the production FSDP×TP(+SP) rules, the arch's rule overrides (e.g. mixtral's
experts→TP-within-expert fallback), and the batch axes present in the mesh.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax

from repro.dist.sharding import MeshRules, _base_rules
from repro.models.config import ArchConfig

__all__ = ["make_production_mesh", "make_mesh", "rules_for", "describe_mesh"]


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {need} devices, found {len(devs)} — "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"BEFORE importing jax (dryrun.py does this)"
        )
    # Auto axes: the sharding rules place arrays with with_sharding_constraint,
    # which jax refuses on the Explicit axes jax.make_mesh defaults to
    return jax.make_mesh(
        tuple(shape),
        tuple(axes),
        devices=devs[:need],
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def rules_for(
    cfg: Optional[ArchConfig],
    mesh: jax.sharding.Mesh,
    *,
    seq_parallel: bool = True,
) -> MeshRules:
    rules = _base_rules(pod="pod" in mesh.axis_names)
    if cfg is not None:
        for name, axis in cfg.rule_overrides:
            rules[name] = axis
    return MeshRules(rules=rules, mesh=mesh, shard_seq_activations=seq_parallel)


def describe_mesh(mesh: jax.sharding.Mesh) -> str:
    return "x".join(
        f"{n}={s}" for n, s in zip(mesh.axis_names, mesh.devices.shape)
    )
