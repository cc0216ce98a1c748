"""The paper's collectives, adapted to the TPU mesh.

Three families, all expressed as `shard_map` bodies over mesh axes:

* ``tree_*``   — pPython's node-aware binary-tree algorithms (paper
  Figs 4/6): log2(P) `ppermute` rounds per hierarchy level, with the
  cross-pod ("off-node") level separated from the in-pod ("in-node")
  level exactly as the paper separates scp-hops from shm-hops.
* ``serial_*`` — pPython's *initial* serialized algorithms (the Fig 7
  baseline): P-1 rounds.
* ``hier_*``   — the beyond-paper production variant: in-pod
  reduce-scatter -> cross-pod all-reduce -> in-pod all-gather.  Wire
  compression (the slow-DCI analogue of the paper's "use the right
  filesystem per level" finding) is layered on by
  ``repro.comms.compression`` intercepting the compat shims these
  schedules already route through.

The native XLA collectives (plain psum/all_gather) play the role of the
paper's mpi4py/OpenMPI-RoCE baseline.

All functions run *inside* shard_map (the jit-level entry point is
``repro.comms.Communicator.run``) and are numerically equivalent to
their flat counterparts — property-tested in
tests/test_collectives_multidev.py on virtual devices.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.comms.compat import (all_gather_tiled as _all_gather,
                                ppermute as _ppermute,
                                psum as _psum,
                                psum_scatter_blocks as _psum_scatter)
from repro.core import topology

Array = jax.Array


def tree_bcast_axis(x: Array, axis: str, root: int = 0) -> Array:
    """Binary-tree broadcast along one mesh axis (in-shard_map).

    The value on rank ``root`` wins; other ranks' payloads are ignored.
    log2(n) ppermute rounds — the paper's optimized broadcast."""
    n = lax.axis_size(axis)
    me = lax.axis_index(axis)
    have = (me == root)
    for rnd in topology.tree_bcast_rounds(n, root):
        recv = _ppermute(x, axis, rnd)
        dsts = jnp.array([d for _, d in rnd], jnp.int32)
        is_dst = jnp.any(me == dsts)
        take = is_dst & ~have
        x = jnp.where(take, recv, x)
        have = have | is_dst
    return x


def serial_bcast_axis(x: Array, axis: str, root: int = 0) -> Array:
    """The paper's initial serialized broadcast: n-1 rounds, root sends to
    one rank per round."""
    n = lax.axis_size(axis)
    me = lax.axis_index(axis)
    for rnd in topology.serial_bcast_rounds(n, root):
        recv = _ppermute(x, axis, rnd)
        (src, dst), = rnd
        x = jnp.where(me == dst, recv, x)
    return x


def tree_reduce_axis(x: Array, axis: str, root: int = 0) -> Array:
    """Binary-tree sum-reduction to ``root`` along one axis (the reduce
    flavour of the paper's agg)."""
    n = lax.axis_size(axis)
    for rnd in topology.tree_gather_rounds(n, root):
        recv = _ppermute(x, axis, rnd)
        me = lax.axis_index(axis)
        dsts = jnp.array([d for _, d in rnd], jnp.int32)
        is_dst = jnp.any(me == dsts)
        x = jnp.where(is_dst, x + recv, x)
    return x


def tree_gather_axis(x: Array, axis: str, root: int = 0) -> Array:
    """Binary-tree concat-gather to ``root`` (paper Fig 4 agg): message
    doubles each round, exactly the paper's growing aggregation buffers.
    Returns (n*shard,) on root; junk elsewhere (masked by caller)."""
    n = lax.axis_size(axis)
    me = lax.axis_index(axis)
    flat = x.reshape(-1)
    local = flat.shape[0]
    buf = flat
    step = 1
    while step < n:
        # senders: ranks at odd multiples of `step` (relative to root)
        pairs = []
        for i in range(0, n, 2 * step):
            j = i + step
            if j < n:
                pairs.append((((j + root) % n), ((i + root) % n)))
        recv = _ppermute(buf, axis, pairs)
        # receivers append; non-receivers keep garbage (masked at the end)
        buf = jnp.concatenate([buf, recv], axis=0)
        step *= 2
    if buf.shape[0] < n * local:  # non-power-of-two: pad
        buf = jnp.pad(buf, (0, n * local - buf.shape[0]))
    # blocks accumulate in root-relative (logical) order; roll back so the
    # concat is in physical rank order for any root
    full = jnp.roll(buf[: n * local].reshape(n, local), root, 0).reshape(-1)
    return jnp.where(me == root, full, jnp.zeros((n * local,), x.dtype))


def pairwise_alltoall_axis(x: Array, axis: str, *, dim: int = 0,
                           serial: bool = False) -> Array:
    """In-shard_map all-to-all along one mesh axis via explicit
    ``ppermute`` rounds (the scheduled-transport analogue of
    ``lax.all_to_all``).

    ``x`` carries one block per destination rank along ``dim`` (size n);
    the result has the same shape with block s along ``dim`` holding rank
    s's block addressed to this rank.  The schedule comes from
    ``topology.pairwise_alltoall_rounds``: disjoint XOR partner pairs for
    power-of-two n (nearest neighbours first), rotation rounds otherwise,
    or one-pair-per-round when ``serial=True`` (the paper's serialized
    baseline).  Round payloads move through ``_ppermute`` (the compat
    shim), so a wire-compression context quantizes them without this
    schedule knowing.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    me = lax.axis_index(axis)

    def exchange(blk, perm):
        return _ppermute(blk, axis, perm)

    out = x
    for kind, arg, perm in topology.pairwise_alltoall_rounds(n, serial):
        if kind == "pair":                  # static (src, dst), one pair
            s, d = arg
            recv = exchange(lax.slice_in_dim(x, d, d + 1, axis=dim), perm)
            keep = lax.slice_in_dim(out, s, s + 1, axis=dim)
            upd = jnp.where(me == d, recv, keep)
            out = lax.dynamic_update_slice_in_dim(out, upd, s, axis=dim)
            continue
        if kind == "xor":                   # partner = me ^ k
            send_to = jnp.bitwise_xor(me, arg)
            recv_from = send_to
        else:                               # rotation by k
            send_to = (me + arg) % n
            recv_from = (me - arg) % n
        blk = lax.dynamic_slice_in_dim(x, send_to, 1, axis=dim)
        recv = exchange(blk, perm)
        out = lax.dynamic_update_slice_in_dim(out, recv, recv_from,
                                              axis=dim)
    return out


def ring_allgather_axis(x: Array, axis: str) -> Array:
    """Ring all-gather via n-1 ppermutes (bandwidth-optimal reference for
    the benchmark harness)."""
    n = lax.axis_size(axis)
    me = lax.axis_index(axis)
    flat = x.reshape(-1)
    local = flat.shape[0]
    out = jnp.zeros((n, local), x.dtype)
    out = lax.dynamic_update_slice(out, flat[None], (me, 0))
    block = flat
    perm = [(i, (i + 1) % n) for i in range(n)]
    for k in range(1, n):
        block = _ppermute(block, axis, perm)
        src = (me - k) % n
        out = lax.dynamic_update_slice(out, block[None], (src, 0))
    return out.reshape((n,) + x.shape)


# ---------------------------------------------------------------------------
# two-level ("node-aware" -> "pod-aware") compositions
# ---------------------------------------------------------------------------

def _axis_roots(root: int, axes: Sequence[str]) -> dict:
    """Decompose a *global* (linear, C-order over ``axes``) root rank
    into its per-axis coordinates — the root each per-axis schedule
    needs.  Sizes are static inside shard_map."""
    sizes = [lax.axis_size(a) for a in axes]
    coords = {}
    for a, n in zip(reversed(tuple(axes)), reversed(sizes)):
        coords[a] = root % n
        root //= n
    return coords


def two_level_bcast(x: Array, *, pod_axis: Optional[str], in_axes:
                    Sequence[str], tree: bool = True, root: int = 0) -> Array:
    """Paper Fig 6: broadcast among pod leaders first (off-node level),
    then within each pod (in-node level).  ``root`` is the global linear
    rank (C-order, pod-major); it is decomposed into per-axis roots so
    each level propagates from the fiber that actually holds the data."""
    fn = tree_bcast_axis if tree else serial_bcast_axis
    axes = ((pod_axis,) if pod_axis else ()) + tuple(in_axes)
    roots = _axis_roots(root, axes)
    if pod_axis is not None:
        x = fn(x, pod_axis, roots[pod_axis])
    for a in in_axes:
        x = fn(x, a, roots[a])
    return x


def two_level_agg(x: Array, *, pod_axis: Optional[str],
                  in_axes: Sequence[str], root: int = 0) -> Array:
    """Paper Fig 4: binary-tree aggregation, in-node level first, then
    across nodes.  Concat semantics; the result lands on global rank
    ``root`` in physical C-order (rank = (((pod) * data) + d) * model
    + m), axes gathered innermost-first to match that layout."""
    axes = ((pod_axis,) if pod_axis else ()) + tuple(in_axes)
    roots = _axis_roots(root, axes)
    for a in reversed(tuple(in_axes)):
        x = tree_gather_axis(x, a, roots[a])
    if pod_axis is not None:
        x = tree_gather_axis(x, pod_axis, roots[pod_axis])
    return x


def hier_allreduce_local(x: Array, *, pod_axis: Optional[str],
                         in_axes: Sequence[str]) -> Array:
    """In-shard_map hierarchical all-reduce (beyond-paper production
    variant): reduce-scatter in-pod -> all-reduce cross-pod -> all-gather
    in-pod.  The cross-pod leg goes through the compat ``psum`` shim, so
    a wire-compression context (``hier_int8`` & friends) quantizes
    exactly that hop.  Falls back to plain psum for shapes that do not
    divide."""
    shape = x.shape
    flat = x.reshape(-1)
    n_in = 1
    for a in in_axes:
        n_in *= lax.axis_size(a)
    if flat.shape[0] % n_in or n_in == 1:
        y = _psum(x, tuple(in_axes))
        if pod_axis is not None:
            y = _psum(y, pod_axis)
        return y
    # in-pod reduce-scatter over the (flattened) composite axis
    shard = _psum_scatter(flat.reshape(n_in, -1), tuple(in_axes))
    if pod_axis is not None:
        shard = _psum(shard, pod_axis)
    out = _all_gather(shard, tuple(in_axes))
    return out.reshape(shape)


def tree_allreduce_local(x: Array, *, pod_axis: Optional[str],
                         in_axes: Sequence[str],
                         tree_bcast: bool = True) -> Array:
    """Paper-faithful all-reduce = agg (tree reduce to leader, Fig 4) +
    broadcast (tree, Fig 6) — what pPython programs compose from agg() and
    bcast().  ``tree_bcast=False`` uses the serialized initial broadcast
    (Fig 7) for the distribution half, so the 'serial' transport is a
    real P-1-round baseline rather than an alias of 'tree'."""
    bcast = tree_bcast_axis if tree_bcast else serial_bcast_axis
    for a in in_axes:
        x = tree_reduce_axis(x, a)
    if pod_axis is not None:
        x = tree_reduce_axis(x, pod_axis)
        x = bcast(x, pod_axis)
    for a in in_axes:
        x = bcast(x, a)
    return x
