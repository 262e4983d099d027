"""Device mesh construction and logical-axis sharding.

Axes (the standard TPU serving/training decomposition):

- ``dp``   — data parallel (batch) — maps across hosts over DCN or chips.
- ``fsdp`` — parameter sharding for training (ZeRO-3 style).
- ``pp``   — pipeline parallel (layer stages, GPipe microbatch schedule
             in ``parallel.pipeline``) — rides DCN or outer ICI.
- ``tp``   — tensor parallel (heads / ffn) — must ride ICI.
- ``sp``   — sequence/context parallel (ring attention) — ICI.
- ``ep``   — expert parallel for MoE.

Parameters and activations are annotated with *logical* axis names
("vocab", "embed", "heads", "mlp", ...) and mapped to physical mesh axes by
the rules table — the MaxText/scaling-book recipe: pick a mesh, annotate,
let XLA insert collectives.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MESH_AXES = ("dp", "fsdp", "pp", "tp", "sp", "ep")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    fsdp: int = 1
    pp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1

    @classmethod
    def from_config(cls, config: Optional[Dict[str, Any]]) -> "MeshConfig":
        if not config:
            return cls()
        return cls(
            dp=int(config.get("dp", 1)),
            fsdp=int(config.get("fsdp", 1)),
            pp=int(config.get("pp", config.get("pipeline-parallelism", 1))),
            tp=int(config.get("tp", config.get("tensor-parallelism", 1))),
            sp=int(config.get("sp", 1)),
            ep=int(config.get("ep", 1)),
        )

    @property
    def size(self) -> int:
        return self.dp * self.fsdp * self.pp * self.tp * self.sp * self.ep

    def axis_sizes(self) -> Tuple[int, ...]:
        return (self.dp, self.fsdp, self.pp, self.tp, self.sp, self.ep)


def validate_mesh(
    config: MeshConfig,
    *,
    num_heads: int,
    num_kv_heads: int,
    intermediate_size: int,
    num_experts: int = 0,
    num_layers: Optional[int] = None,
    allow_pp: bool = False,
) -> None:
    """Reject mesh/model combinations that would silently misbehave.

    Shared by the serving engine and the trainer so both fail with the
    same actionable errors instead of opaque XLA sharding diagnostics.
    """
    if config.tp > 1:
        for name, size in (
            ("num_kv_heads", num_kv_heads),
            ("num_heads", num_heads),
            ("intermediate_size", intermediate_size),
        ):
            if size % config.tp != 0:
                raise ValueError(f"tp={config.tp} must divide {name}={size}")
    if config.ep > 1:
        if not num_experts:
            raise ValueError(
                f"ep={config.ep} requires an MoE model (num_experts > 0); "
                "this model is dense"
            )
        if num_experts % config.ep != 0:
            raise ValueError(
                f"ep={config.ep} must divide num_experts={num_experts}"
            )
    if config.pp > 1:
        if not allow_pp:
            raise ValueError(
                f"pp={config.pp} is only supported by the pipeline trainer "
                "(parallel.pipeline); this component has no pipeline "
                "schedule — use tp/dp axes instead"
            )
        if num_layers is not None and num_layers % config.pp != 0:
            raise ValueError(
                f"pp={config.pp} must divide num_layers={num_layers}"
            )
        if config.fsdp > 1 or config.tp > 1 or config.sp > 1:
            # the pipeline shard_map only uses the pp and dp axes; other
            # axes would replicate params/activations and waste devices
            raise ValueError(
                f"pp={config.pp} composes only with dp for now "
                f"(got fsdp={config.fsdp}, tp={config.tp}, sp={config.sp})"
            )


def build_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[Any]] = None,
) -> Mesh:
    """Build the named mesh. With no config, all devices go to ``tp`` —
    the right default for single-host serving (ICI all-reduce)."""
    devices = list(devices if devices is not None else jax.devices())
    if config is None:
        config = MeshConfig(tp=len(devices))
    if config.size != len(devices):
        raise ValueError(
            f"mesh {config} needs {config.size} devices, have {len(devices)}"
        )
    array = np.asarray(devices).reshape(config.axis_sizes())
    return Mesh(array, MESH_AXES)


# logical axis → candidate physical axes (first that fits wins; None =
# replicated). Mirrors the MaxText-style sharding-rule table.
DEFAULT_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "batch": ("dp", "fsdp"),
    "sequence": ("sp",),
    "vocab": ("tp",),
    "embed": ("fsdp",),
    "heads": ("tp",),
    "kv_heads": ("tp",),
    "head_dim": (),
    "mlp": ("tp",),
    # the stacked-layer axis shards over pp ONLY when the pipeline engine
    # is driving (pp>1 meshes are used exclusively by parallel.pipeline);
    # on pp=1 meshes the rule is skipped and layers stay replicated
    "layers": ("pp",),
    "cache_batch": (),
    "cache_sequence": (),
    "expert": ("ep",),
}


def logical_to_physical(
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Optional[Dict[str, Tuple[Optional[str], ...]]] = None,
) -> PartitionSpec:
    """Map logical axis names to a PartitionSpec under the mesh, skipping
    axes whose mesh size is 1 (so the same annotations work from 1 chip to
    a full slice)."""
    rules = rules or DEFAULT_RULES
    used = set()
    spec: List[Optional[str]] = []
    for logical in logical_axes:
        chosen: Optional[str] = None
        if logical is not None:
            for candidate in rules.get(logical, ()):
                if candidate is None or candidate in used:
                    continue
                if mesh.shape.get(candidate, 1) > 1:
                    chosen = candidate
                    used.add(candidate)
                    break
        spec.append(chosen)
    # trailing replicated axes are dropped, as jit writes the specs of
    # its outputs: a cache placed as (None, 'tp', None) and rethreaded as
    # (None, 'tp') is one sharding and two jit-cache keys, and every
    # program precompiled against the first compiles again in traffic
    while spec and spec[-1] is None:
        spec.pop()
    return PartitionSpec(*spec)


class LogicalAxes:
    """Leaf-safe container of logical axis names for one parameter (a bare
    tuple would be traversed as a pytree container by ``jax.tree.map``)."""

    __slots__ = ("names",)

    def __init__(self, *names: Optional[str]) -> None:
        self.names = tuple(names)

    def __repr__(self) -> str:
        return f"L{self.names!r}"


L = LogicalAxes


def shard_params(params: Any, logical_axes: Any, mesh: Mesh, rules=None) -> Any:
    """Device-put a parameter pytree according to its logical-axes pytree
    (leaves of ``logical_axes`` are :class:`LogicalAxes`)."""

    def place(leaf, axes: LogicalAxes):
        spec = logical_to_physical(axes.names, mesh, rules)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(place, params, logical_axes)


def param_shardings(logical_axes: Any, mesh: Mesh, rules=None) -> Any:
    """NamedSharding pytree from a LogicalAxes pytree (for jit in/out
    shardings)."""

    def to_sharding(axes: LogicalAxes):
        return NamedSharding(mesh, logical_to_physical(axes.names, mesh, rules))

    return jax.tree.map(to_sharding, logical_axes)
