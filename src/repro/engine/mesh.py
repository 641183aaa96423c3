"""Scenario x policy-group device mesh (DESIGN.md §9).

The scenario axis is embarrassingly parallel — each scenario's price path,
per-bid views, and counterfactual costs are independent; only the regret
fold crosses scenarios — so sharding it is pure data parallelism along a
mesh axis named ``"data"``.  The eval-group axis (bid x policy-group rows
of the grid plan) is *also* independent per group, so grids whose group
axis dwarfs S (exp1's 175-policy sweeps) shard it along a second mesh axis
named ``"model"``.  ``GridMesh`` maps the logical axes ``scenario ->
"data"``, ``group -> "model"`` and ``bid -> None`` itself; a 1-wide
``"model"`` axis reproduces the 1-D behavior bitwise.

``GridMesh`` is hashable (it keys the backends' compiled-program caches)
and owns the padding contract for BOTH axes:

* scenario axis — a chunk of K scenarios is padded to ``pad(K)`` rows,
  the LAST row repeated, so every ``"data"`` shard holds the same row
  count;
* group axis — the eval-group list is padded to ``pad_groups(G)`` entries,
  the LAST group repeated, so every ``"model"`` shard owns the same number
  of whole groups.

Padded lanes carry real (duplicated) data, are masked out of every
reduction, and are sliced off at the splice before results reach the
caller (:func:`edge_repeat` / the ``[:K]`` and ``[:, :G]`` slices).  See
DESIGN.md §9 for the placement diagram.

This module imports jax lazily so ``repro.engine`` stays importable in
environments without it (the numpy oracle path).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

__all__ = ["GridMesh", "as_scenario_mesh", "pad_to", "edge_repeat"]

# Logical axis -> mesh axis (None: replicated).
_LOGICAL_AXES = {"scenario": "data", "group": "model", "bid": None}


def pad_to(k: int, n: int) -> int:
    """Smallest multiple of ``n`` that is ``>= k`` (the padded lane count)."""
    return -(-k // n) * n


def edge_repeat(a: np.ndarray, rows: int) -> np.ndarray:
    """Pad the leading axis to ``rows`` by repeating the last entry.

    The padding contract for both mesh axes: padded lanes are real
    (duplicated) data, never NaN/zero filler, so every shard computes a
    well-posed problem and the splice just drops the extra lanes.
    """
    k = a.shape[0]
    if rows == k:
        return a
    if rows < k:
        raise ValueError(f"cannot pad {k} rows down to {rows}")
    reps = np.repeat(a[-1:], rows - k, axis=0)
    return np.concatenate([a, reps], axis=0)


@dataclasses.dataclass(frozen=True)
class GridMesh:
    """A 2-D ``("data", "model")`` mesh plus its logical-axis map.

    Frozen and hashable — ``backend_jax`` and the learn-fold cache one
    compiled ``shard_map`` program per (mesh, shape) key.  A 1-D raw
    ``"data"`` mesh (or ``model_devices=1``) degrades to pure scenario
    data-parallelism, bitwise identical to the pre-2-D behavior.
    """

    mesh: Any                 # jax.sharding.Mesh (hashable)

    @classmethod
    def create(cls, n_devices: int | None = None,
               model_devices: int = 1) -> "GridMesh":
        """Mesh of ``n_devices x model_devices`` visible devices.

        ``n_devices`` (default: all remaining after the model axis) shards
        the scenario axis as ``"data"``; ``model_devices`` shards the
        eval-group axis as ``"model"``.  Asking for more devices than are
        visible raises ``ValueError`` naming both counts.
        """
        import jax

        avail = len(jax.devices())
        m = int(model_devices)
        if m < 1:
            raise ValueError(
                f"mesh needs >= 1 model device (got {model_devices})")
        n = max(avail // m, 1) if n_devices is None else int(n_devices)
        if n < 1:
            raise ValueError(f"mesh needs >= 1 device (got {n_devices})")
        if n * m > avail:
            raise ValueError(
                f"requested a {n}x{m} ({n * m}-device) scenario x group mesh "
                f"but only {avail} device(s) are visible (on CPU, "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N fakes "
                f"N host devices)")
        shape, axes = ((n, m), ("data", "model")) if m > 1 else \
            ((n,), ("data",))
        return cls(mesh=jax.make_mesh(shape, axes))

    @property
    def n_shards(self) -> int:
        return self.mesh.devices.size

    @property
    def data_shards(self) -> int:
        """Shards along the scenario (``"data"``) axis."""
        return self.mesh.shape["data"]

    @property
    def model_shards(self) -> int:
        """Shards along the eval-group (``"model"``) axis (1 on 1-D meshes)."""
        return self.mesh.shape.get("model", 1)

    def pad(self, k: int) -> int:
        """Rows after padding k scenarios to a multiple of ``data_shards``."""
        return pad_to(k, self.data_shards)

    def pad_groups(self, g: int) -> int:
        """Entries after padding g eval groups to a multiple of
        ``model_shards`` (whole groups per ``"model"`` shard)."""
        return pad_to(g, self.model_shards)

    def spec(self, *logical_axes: str | None):
        """PartitionSpec through the logical-axis map (``"scenario" ->
        "data"``, ``"group" -> "model"``); a mesh axis this mesh lacks
        maps to None (replicated)."""
        from jax.sharding import PartitionSpec

        names = self.mesh.axis_names
        return PartitionSpec(*(
            ax if ax in names else None
            for ax in (_LOGICAL_AXES.get(a) for a in logical_axes)))

    def sharding(self, *logical_axes: str | None):
        """NamedSharding placing the named logical axes on this mesh."""
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, self.spec(*logical_axes))

    def pad_rows(self, a: np.ndarray) -> np.ndarray:
        """Pad a leading-scenario host array to ``pad(len)`` rows (repeat
        the last row — real data, masked/sliced away downstream)."""
        return edge_repeat(a, self.pad(a.shape[0]))

    def put_rows(self, a):
        """Pad + device_put a leading-scenario array sharded over the mesh
        (``"data"`` only; replicated over ``"model"``)."""
        import jax

        return jax.device_put(self.pad_rows(np.asarray(a)),
                              self.sharding("scenario"))


def as_scenario_mesh(mesh) -> GridMesh | None:
    """Normalize every accepted ``mesh=`` argument.

    Accepts ``None`` (unsharded), a ``GridMesh``, an int
    (scenario-shard count, at most the visible devices), or a raw jax
    ``Mesh`` whose axes include ``"data"`` (a ``"model"`` axis, when
    present, shards the eval-group axis).
    """
    if mesh is None or isinstance(mesh, GridMesh):
        return mesh
    if isinstance(mesh, bool):
        raise ValueError(f"mesh must be None, an int shard count, a "
                         f"GridMesh, or a jax Mesh (got {mesh!r})")
    if isinstance(mesh, (int, np.integer)):
        return GridMesh.create(int(mesh))
    try:
        from jax.sharding import Mesh
    except Exception as e:  # pragma: no cover - jax-less environment
        raise ValueError(
            "mesh= requires importable jax (the sharded scenario axis is "
            "a jax-backend feature)") from e
    if isinstance(mesh, Mesh):
        if "data" not in mesh.axis_names:
            raise ValueError(
                f"scenario mesh needs a 'data' axis (got axes "
                f"{tuple(mesh.axis_names)}); build one with "
                f"GridMesh.create(n) or jax.make_mesh((n,), ('data',))")
        return GridMesh(mesh=mesh)
    raise ValueError(f"mesh must be None, an int shard count, a "
                     f"GridMesh, or a jax Mesh (got {type(mesh)})")
