"""The mesh-bound Communicator: the full PythonMPI surface in one object.

The paper's PGAS layer programs against a tiny messaging API (SendMsg /
RecvMsg / agg / bcast / barrier) precisely so "any other communication
library could be substituted".  ``Communicator`` is that API here:
constructed once from a mesh (hierarchy derived in one place by
``Topology.from_mesh``), it exposes

  in-shard_map ops   send / recv / sendrecv / barrier / bcast / agg /
                     scatter / allreduce / reduce_scatter / allgather /
                     alltoall / alltoallv
  jit-level entry    comm.run(fn, *args) / comm.wrap(fn)  — so callers
                     never hand-roll their own ``shard_map``

with per-op algorithm selection via ``CommSpec`` and the transport
registry (native / tree / serial / hier / hier_int8), plus optional wire
compression (``CommSpec.compression`` wraps every transport in a
``CompressedTransport``) and error-feedback allreduce.  All data ops are
pytree-aware.  See repro/comms/README.md for the paper-function mapping.
"""
from __future__ import annotations

import dataclasses
from math import prod
from typing import Any, Callable, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.comms import compat, faults
from repro.comms import compression as compression_lib
from repro.comms.compression import CompressionSpec
from repro.comms.topology import Topology
from repro.comms.transports import (Transport, available_transports,
                                    get_transport)

Array = jax.Array

_OPS = ("allreduce", "bcast", "agg", "reduce_scatter", "allgather",
        "scatter", "alltoall")


@dataclasses.dataclass(frozen=True)
class CommSpec:
    """Per-op transport selection (names from the transport registry).

    ``overlap`` is a scheduling hint, not a transport: consumers that can
    pipeline (the train-step gradient exchange) issue each collective one
    slot *behind* the compute that produced its operand, so the exchange
    of slot *i* is in flight while slot *i+1* computes.  Transports are
    oblivious — the same algorithms run either way.

    ``compression`` composes a :class:`CompressionSpec` with every op's
    transport (``CompressedTransport``); its ``error_feedback`` flag is,
    like ``overlap``, a consumer hint — ``allreduce_ef`` and the train
    step act on it, transports are oblivious.
    """

    allreduce: str = "native"
    bcast: str = "native"
    agg: str = "native"
    reduce_scatter: str = "native"
    allgather: str = "native"
    scatter: str = "native"
    alltoall: str = "native"            # also drives alltoallv
    overlap: bool = False               # pipeline collectives behind compute
    compression: Optional[CompressionSpec] = None

    @classmethod
    def from_flag(cls, flag: str) -> "CommSpec":
        """Map a CLI-style algorithm flag (--grad-comms) to a spec.

        Grammar: ``<transport>[_<dtype>][_all][_ef][_overlap]`` —
        ``<transport>`` is any registered name, ``<dtype>`` one of
        int8/fp8/int4 (wire compression, cross-pod scope by default),
        ``_all`` widens compression to every leg, ``_ef`` enables
        error-feedback accumulation, ``_overlap`` the pipelined
        schedule.  Unknown combinations raise ``ValueError`` at parse
        time (not deep in tracing).  'auto' (GSPMD, no explicit comms)
        must be handled by the caller *before* building a Communicator.
        """
        if flag == "auto":
            raise ValueError("grad_comms='auto' means GSPMD handles the "
                             "exchange; no Communicator is involved")
        names = available_transports()

        def fail():
            raise ValueError(
                f"unknown comms flag {flag!r}; expected "
                f"<transport>[_<dtype>][_all][_ef][_overlap] with "
                f"transport in {sorted(names)} and dtype in "
                f"{list(compression_lib.DTYPES)}")

        rest, overlap = flag, False
        if rest.endswith("_overlap"):
            rest, overlap = rest[:-len("_overlap")], True
        ef = False
        if rest.endswith("_ef"):
            rest, ef = rest[:-len("_ef")], True
        scope = "cross-pod"
        if rest.endswith("_all"):
            rest, scope = rest[:-len("_all")], "all"

        cspec: Optional[CompressionSpec] = None
        if rest in names:
            base = rest
            if base == "hier_int8" and (ef or scope == "all"):
                # modifiers need an explicit spec; decompose the alias
                base = "hier"
                cspec = dataclasses.replace(compression_lib.LEGACY_INT8,
                                            error_feedback=ef, scope=scope)
            elif ef or scope == "all":
                fail()      # _ef/_all only modify a compressed mode
        else:
            base, _, dtype = rest.rpartition("_")
            if (dtype not in compression_lib.DTYPES or base not in names
                    or base == "hier_int8"):
                fail()
            cspec = CompressionSpec(dtype=dtype, scope=scope,
                                    error_feedback=ef)
        return cls(**{op: base for op in _OPS}, overlap=overlap,
                   compression=cspec)


def _as_spec(spec: Union[str, CommSpec, None]) -> CommSpec:
    if spec is None:
        return CommSpec()
    if isinstance(spec, str):
        return CommSpec.from_flag(spec)
    return spec


class Communicator:
    """Mesh-bound SPMD messaging object (see module docstring).

    Data-op methods run *inside* shard_map over ``self.axes`` — either a
    shard_map the caller already has, or one built by ``self.run`` /
    ``self.wrap``.  Ranks are linear C-order over ``self.axes`` (pod
    level first), matching the paper's leader-on-rank-0 convention.
    """

    def __init__(self, mesh: Mesh,
                 spec: Union[str, CommSpec, None] = None,
                 axes: Optional[Sequence[str]] = None):
        self.mesh = mesh
        self.spec = _as_spec(spec)
        self.topo = Topology.from_mesh(mesh, axes=axes)
        # the armed FaultPlan (if any) is captured at construction:
        # maybe_wrap is the identity when chaos is disarmed, so the
        # common path carries zero wrapper overhead
        self.fault_plan = faults.active_plan()

        def make(op: str) -> Transport:
            t = get_transport(getattr(self.spec, op), self.topo)
            if self.spec.compression is not None:
                # compression sits inside chaos: fault retries corrupt
                # the float payload, the clean attempt is the compressed
                # exchange
                t = compression_lib.CompressedTransport(
                    t, self.spec.compression)
            return faults.maybe_wrap(t, self.fault_plan)

        self._t: Dict[str, Transport] = {op: make(op) for op in _OPS}
        self._sync_fn = None

    # -------------------------------------------------------------- identity
    @property
    def axes(self):
        return self.topo.axes

    @property
    def size(self) -> int:
        return self.topo.n_ranks

    def rank(self):
        """Linear rank of the calling shard (traced; in-shard_map)."""
        return self.topo.rank()

    # -------------------------------------------------- point-to-point (p2p)
    def sendrecv(self, x: Any, pairs: Sequence[tuple]) -> Any:
        """Scheduled p2p rounds (the primitive under SendMsg/RecvMsg):
        each (src, dst) pair moves src's leaf values to dst; every other
        rank keeps its own.  Pairs are static linear ranks."""
        pairs = [(self._check_rank(int(s), "src"),
                  self._check_rank(int(d), "dst")) for s, d in pairs]
        dsts = jnp.asarray([d for _, d in pairs], jnp.int32)
        me = self.topo.rank()
        is_dst = jnp.any(me == dsts)

        def leaf(v):
            recv = compat.ppermute(v, self.axes, pairs)
            return jnp.where(is_dst, recv, v)
        return jax.tree.map(leaf, x)

    def send(self, x: Any, dst: int, *, src: int = 0) -> Any:
        """pPython SendMsg: deliver rank ``src``'s value of ``x`` to rank
        ``dst`` (SPMD: both endpoints — and everyone else — execute the
        same call; non-participants pass ``x`` through)."""
        return self.sendrecv(x, [(src, dst)])

    def recv(self, x: Any, src: int, *, dst: int) -> Any:
        """pPython RecvMsg: the receiving spelling of ``send`` — rank
        ``dst`` ends up holding rank ``src``'s value."""
        return self.sendrecv(x, [(src, dst)])

    # ------------------------------------------------------------ collectives
    def barrier(self) -> Array:
        """In-shard_map rank barrier: a zero-byte-ish reduction every rank
        must reach.  Returns a 0-d token to thread into downstream ops."""
        return compat.psum(jnp.zeros((), jnp.float32), self.axes)

    def allreduce(self, x: Any) -> Any:
        return jax.tree.map(self._t["allreduce"].allreduce, x)

    def allreduce_ef(self, x: Any, err: Any):
        """Error-feedback allreduce (in-shard_map): ``v = x + err`` is
        projected through the wire's lossy C(.) *locally* (``qdq``)
        before the exchange; returns ``(allreduce(C(v)), v - C(v))`` —
        the residual to add into the next step's operand.  Because C(v)
        is already on the quantization grid, the first wire hop loses
        nothing; EF re-injects what C itself dropped.  With no
        compression spec C is the identity and the residual stays
        zero."""
        v = jax.tree.map(lambda a, e: a + e.astype(a.dtype), x, err)
        cspec = self.spec.compression
        if cspec is None:
            return self.allreduce(v), jax.tree.map(jnp.zeros_like, v)
        c = jax.tree.map(lambda a: compression_lib.qdq(a, cspec), v)
        resid = jax.tree.map(lambda a, b: a - b, v, c)
        return self.allreduce(c), resid

    def _check_rank(self, rank: int, what: str) -> int:
        if not 0 <= rank < self.size:
            raise ValueError(f"{what}={rank} out of range for "
                             f"{self.size} ranks over axes {self.axes}")
        return rank

    def bcast(self, x: Any, root: int = 0) -> Any:
        self._check_rank(root, "root")
        return jax.tree.map(lambda v: self._t["bcast"].bcast(v, root), x)

    def agg(self, x: Any, root: int = 0) -> Any:
        """Concat-gather every rank's leaf onto ``root`` (flat, (n*size,)
        per leaf); zeros elsewhere — pPython's agg()."""
        self._check_rank(root, "root")
        return jax.tree.map(lambda v: self._t["agg"].agg(v, root), x)

    def scatter(self, x: Any, root: int = 0) -> Any:
        """Inverse of ``agg`` (pPython's root-distributes direction, Fig
        6): rank ``root``'s flat leaf is split into ``size`` blocks and
        rank i keeps block i (zero-padded to equal blocks)."""
        self._check_rank(root, "root")
        return jax.tree.map(lambda v: self._t["scatter"].scatter(v, root), x)

    def reduce_scatter(self, x: Any) -> Any:
        return jax.tree.map(self._t["reduce_scatter"].reduce_scatter, x)

    def allgather(self, x: Any) -> Any:
        """agg visible on every rank (pPython's agg() + bcast)."""
        return jax.tree.map(self._t["allgather"].allgather, x)

    def alltoall(self, x: Any) -> Any:
        """MPI Alltoall — the token-routed exchange under expert-parallel
        MoE dispatch: each leaf's leading dim splits into ``size`` equal
        per-destination blocks; rank i's block j arrives as rank j's
        block i.  Algorithm from ``spec.alltoall`` (XLA ``all_to_all``
        for 'native'; scheduled pairwise ppermute rounds otherwise)."""
        return jax.tree.map(self._t["alltoall"].alltoall, x)

    def alltoallv(self, x: Any, counts) -> Any:
        """Ragged Alltoall (MPI Alltoallv): ``counts`` is a static
        (size, size) matrix, ``counts[i][j]`` = rows rank i sends to
        rank j.  Leaf rows are packed destination-ordered on the way in
        and source-ordered (zero-padded tail) on the way out; see
        ``Transport.alltoallv`` for the exact layout.  Uses the
        ``spec.alltoall`` transport."""
        counts = tuple(tuple(int(c) for c in r) for r in counts)
        if len(counts) != self.size or any(len(r) != self.size
                                           for r in counts):
            raise ValueError(f"counts must be {self.size}x{self.size} "
                             f"for axes {self.axes}")
        return jax.tree.map(
            lambda v: self._t["alltoall"].alltoallv(v, counts), x)

    def redistribute(self, x: Any, src_map, dst_map,
                     shape: Sequence[int]) -> Any:
        """Streamed PGAS redistribution (in-shard_map): move this rank's
        padded local block of a distributed array from ``src_map``'s
        layout to ``dst_map``'s in ONE scheduled Alltoallv — the
        capability pMatlab/pPython name as the library's core, with no
        global materialization and no checkpoint round-trip.

        Each leaf is this rank's OLD block (shape ``(1, *old_pad)`` as
        shard_map presents Dmat storage, or ``old_pad`` bare); the
        result is this rank's NEW block in the same convention.  The
        (counts, send, recv) plan is static numpy computed once per
        (maps, shape) — see :func:`repro.core.dmap.redistribution_plan`;
        the wire exchange runs over the ``spec.alltoall`` transport, so
        tree/serial/hier schedules (and chaos fault injection) apply
        unchanged."""
        from repro.core import dmap as dmap_lib
        shape = tuple(int(s) for s in shape)
        counts, send_idx, recv_idx = dmap_lib.redistribution_plan(
            src_map, dst_map, shape, self.size)
        old_size = int(prod(src_map.local_shape(shape)))
        dst_pad = dst_map.local_shape(shape)
        new_size = int(prod(dst_pad))
        me = self.topo.rank()
        sidx = jnp.take(jnp.asarray(send_idx), me, axis=0)
        ridx = jnp.take(jnp.asarray(recv_idx), me, axis=0)

        def leaf(v):
            lead = v.ndim == len(shape) + 1 and v.shape[0] == 1
            flat = v.reshape(-1)
            if flat.shape[0] != old_size:
                raise ValueError(
                    f"leaf holds {flat.shape[0]} elements; src_map's "
                    f"padded local block is {old_size}")
            payload = jnp.take(flat, jnp.clip(sidx, 0, old_size - 1),
                               axis=0)[:, None]
            rows = self._t["alltoall"].alltoallv(
                payload, counts)[:, 0]
            # scatter source-ordered rows to their cells; -1 padding
            # rows land in a sacrificial slot past the block
            buf = jnp.zeros((new_size + 1,), v.dtype)
            buf = buf.at[jnp.where(ridx >= 0, ridx, new_size)].set(
                rows.astype(v.dtype))
            out = buf[:new_size].reshape(dst_pad)
            return out[None] if lead else out
        return jax.tree.map(leaf, x)

    # ------------------------------------------------------- jit-level entry
    def wrap(self, fn: Callable, *, in_specs=None, out_specs=None,
             manual_axes: Optional[Sequence[str]] = None) -> Callable:
        """shard_map ``fn`` over this communicator's mesh — THE way to
        enter comm ops from jit level; callers never build shard_maps.

        Defaults: replicated in/out (``P()``).  ``manual_axes`` limits
        manual mapping to a subset (e.g. batch axes), leaving the rest to
        GSPMD — such partial maps must run under ``jax.jit``.
        """
        if in_specs is None:
            in_specs = P()
        if out_specs is None:
            out_specs = P()
        return compat.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                out_specs=out_specs, manual_axes=manual_axes)

    def run(self, fn: Callable, *args, in_specs=None, out_specs=None,
            manual_axes: Optional[Sequence[str]] = None):
        """Run ``fn`` (a body using this communicator's ops) under
        shard_map on ``args``."""
        if in_specs is None and args:
            in_specs = tuple(P() for _ in args)
        return self.wrap(fn, in_specs=in_specs, out_specs=out_specs,
                         manual_axes=manual_axes)(*args)

    def sync(self) -> None:
        """Host-blocking device barrier (jit-level ``barrier``): returns
        once every rank of the mesh has reached it."""
        if self._sync_fn is None:
            self._sync_fn = jax.jit(
                self.wrap(lambda t: t + self.barrier(),
                          in_specs=(P(),), out_specs=P()))
        jax.block_until_ready(self._sync_fn(jnp.zeros((), jnp.float32)))

    # ------------------------------------------------------------- caching
    _CACHE: Dict[Any, "Communicator"] = {}

    @classmethod
    def for_mesh(cls, mesh: Mesh,
                 spec: Union[str, CommSpec, None] = None,
                 axes: Optional[Sequence[str]] = None) -> "Communicator":
        """Memoized constructor — hot paths (Dmat ops) share one
        Communicator (and its jitted sync) per (mesh, spec, axes)."""
        key = (mesh, _as_spec(spec), None if axes is None else tuple(axes),
               faults.active_plan())
        comm = cls._CACHE.get(key)
        if comm is None:
            comm = cls._CACHE[key] = cls(mesh, spec, axes)
        return comm
