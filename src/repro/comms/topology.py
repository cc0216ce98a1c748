"""Mesh-derived communication topology.

The paper's two hierarchy levels (in-node / off-node, Figs 4 & 6) map
onto the mesh axes: ``pod`` is the off-node (slow DCI) level, every
other axis the in-node (ICI) level.  ``Topology.from_mesh`` derives the
split ONCE — it replaces the ``pod = "pod" if "pod" in mesh.axis_names
else None`` block that used to be copy-pasted into every consumer.

A Topology can cover a *subset* of the mesh axes (e.g. the gradient
exchange runs over the batch axes only, leaving the model axis to
GSPMD): pass ``axes=`` to restrict it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

from jax import lax
from jax.sharding import Mesh

POD_AXIS = "pod"


@dataclasses.dataclass(frozen=True)
class Topology:
    """The (pod_axis, in_axes) hierarchy plus static per-axis sizes."""

    pod_axis: Optional[str]
    in_axes: Tuple[str, ...]
    axis_sizes: Tuple[int, ...]        # aligned with ``self.axes``

    @classmethod
    def from_mesh(cls, mesh: Mesh,
                  axes: Optional[Sequence[str]] = None) -> "Topology":
        """Derive the hierarchy from a mesh (optionally restricted to a
        subset of its axes).  The ``pod`` axis, when present, is always
        hoisted to the front — ranks are numbered pod-major (off-node
        level first) regardless of the order given; the remaining axes
        keep their given order."""
        names = tuple(mesh.axis_names) if axes is None else tuple(axes)
        for a in names:
            if a not in mesh.axis_names:
                raise ValueError(f"axis {a!r} not in mesh {mesh.axis_names}")
        pod = POD_AXIS if POD_AXIS in names else None
        in_axes = tuple(a for a in names if a != POD_AXIS)
        ordered = ((pod,) if pod else ()) + in_axes
        sizes = tuple(mesh.shape[a] for a in ordered)
        return cls(pod_axis=pod, in_axes=in_axes, axis_sizes=sizes)

    # ------------------------------------------------------------ static
    @property
    def axes(self) -> Tuple[str, ...]:
        """All participating axes, pod (off-node level) first — the
        C-order rank layout every schedule in core.topology assumes."""
        return ((self.pod_axis,) if self.pod_axis else ()) + self.in_axes

    @property
    def n_ranks(self) -> int:
        n = 1
        for s in self.axis_sizes:
            n *= s
        return n

    @property
    def pod_size(self) -> int:
        return self.axis_sizes[0] if self.pod_axis else 1

    @property
    def in_size(self) -> int:
        return self.n_ranks // self.pod_size

    # ------------------------------------------------- traced (in-shard_map)
    def rank(self):
        """Linear rank of the calling shard (traced value)."""
        return lax.axis_index(self.axes)

    def size(self) -> int:
        """Rank count as seen inside shard_map (== n_ranks)."""
        return lax.axis_size(self.axes)
