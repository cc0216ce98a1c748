"""The wire primitives the comms layer builds on.

Every schedule in this package moves data through the five primitives
below (``psum`` / ``ppermute`` / ``all_gather_tiled`` /
``all_to_all_blocks`` / ``psum_scatter_blocks``) rather than calling
``lax`` directly.  They are thin ``lax`` calls plus one hook: while a
wire-compression context is active (``repro.comms.compression``), an
in-scope floating payload is handed to the compressor instead, which
quantizes it, re-enters these primitives with integer payloads + scales,
and dequantizes.  ``shard_map`` maps the package's ``manual_axes``
convention onto ``jax.shard_map``'s ``axis_names``.
"""
from __future__ import annotations

import contextvars
from typing import Callable, Optional, Sequence

import jax
from jax import lax


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              manual_axes: Optional[Sequence[str]] = None,
              check: bool = False) -> Callable:
    """``jax.shard_map`` over ``mesh``.

    ``manual_axes`` — axes mapped manually (the body sees per-shard
    blocks and may use collectives over them); every other mesh axis
    stays automatic (GSPMD).  None means fully manual.  Partial-manual
    maps require the call to happen under ``jax.jit``.
    """
    kwargs = {}
    if manual_axes is not None:
        manual = frozenset(manual_axes)
        if frozenset(mesh.axis_names) - manual:
            kwargs["axis_names"] = manual
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check, **kwargs)


# Wire-compression context (set by repro.comms.compression.compressing):
# when active, the five wire primitives below hand in-scope floating
# payloads to the handler.  compat never imports compression — the
# dependency points one way.
_COMPRESS: contextvars.ContextVar = contextvars.ContextVar(
    "comms_wire_compression", default=None)


def psum(x, axis):
    c = _COMPRESS.get()
    if c is not None and c.applies(axis, x):
        return c.psum(x, axis)
    return lax.psum(x, axis)


def ppermute(x, axis, perm):
    c = _COMPRESS.get()
    if c is not None and c.applies(axis, x):
        return c.ppermute(x, axis, perm)
    return lax.ppermute(x, axis, perm)


def all_gather_tiled(x, axis):
    """Tiled concat-gather of a flat per-rank block along ``axis``."""
    c = _COMPRESS.get()
    if c is not None and c.applies(axis, x):
        return c.all_gather(x, axis)
    return lax.all_gather(x, axis, axis=0, tiled=True)


def all_to_all_blocks(x, axis, dim=0):
    """Single-axis ``lax.all_to_all`` with split and concat on the same
    dim: ``x`` has one block per destination along ``dim`` (size n =
    ranks on ``axis``); the result holds one block per *source* (block s
    = rank s's block addressed to this rank)."""
    c = _COMPRESS.get()
    if c is not None and c.applies(axis, x):
        return c.all_to_all(x, axis, dim)
    return lax.all_to_all(x, axis, dim, dim, tiled=False)


def psum_scatter_blocks(x, axis):
    """``lax.psum_scatter`` of ``x`` shaped (n_ranks_along_axis, blk):
    global sum, each rank keeping its own block."""
    c = _COMPRESS.get()
    if c is not None and c.applies(axis, x):
        return c.psum_scatter(x, axis)
    return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=False)
