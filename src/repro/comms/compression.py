"""Composable compressed communication with error feedback.

The paper's headline finding is that large-message communication is
bandwidth-bound: at scale, bytes-on-wire dominate both PythonMPI and
mpi4py.  ``hier_int8`` proved that cross-pod int8 compression recovers
bandwidth, but it was a one-off baked into one transport.  This module
generalizes it into a layer any registered transport composes with:

* :class:`CompressionSpec` — what to quantize (``dtype`` int8 / fp8-e4m3
  / int4-packed), at what granularity (``block`` elements per float32
  amax scale; ``None`` = per-tensor), on which legs (``scope``
  'cross-pod' = pod-axis hops only, 'all' = every leg), and how to carry
  reductions (``reduce`` 'gather' = exchange quantized payloads and sum
  after dequant — true wire reduction; 'qsum' = pmax-shared scale +
  exact int32 psum — the legacy ``hier_int8`` arithmetic, bit-for-bit).
* :class:`CompressedTransport` — wraps any transport.  It does NOT
  reimplement any schedule: it enters a context under which the compat
  wire primitives (``ppermute`` / ``all_gather_tiled`` / ``psum`` /
  ``psum_scatter_blocks`` / ``all_to_all_blocks``) intercept floating
  payloads on in-scope axes, so tree rounds, hier legs, and native
  collectives all move quantized bytes without knowing it.
* quantize/dequantize — the layout-aware per-block formulation:
  flatten -> pad -> reshape (blocks, B) -> per-block amax scale -> cast
  (-> nibble-pack for int4).  Per-block scales bound the error by the
  block's own dynamic range instead of the tensor's.
* error feedback — ``qdq`` is the local lossy projection C(x); EF keeps
  ``e' = v - C(v)`` where ``v = g + e`` and sends C(v), so quantization
  error is re-injected into the next step instead of lost
  (``Communicator.allreduce_ef`` / the ``*_ef`` grad-comms modes).

``hier_int8`` is re-registered here as ``hier`` + :data:`LEGACY_INT8`
(per-tensor qsum, cross-pod) — same name, same bits, one code path.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import FrozenSet, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.comms import compat
from repro.comms import transports as transports_lib
from repro.comms.transports import Transport

Array = jax.Array

DTYPES = ("int8", "fp8", "int4")
SCOPES = ("cross-pod", "all")
REDUCES = ("gather", "qsum")

_QMAX = {"int8": 127.0, "int4": 7.0, "fp8": 448.0}


@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """How to compress wire payloads (see module docstring).

    ``dtype``  — int8 | fp8 (e4m3) | int4 (two values per byte).
    ``block``  — elements per float32 scale (layout-aware per-block
                 amax); ``None`` = one scale per tensor (the legacy
                 formulation).  Must be even for int4.
    ``scope``  — 'cross-pod' (only hops over the topology's pod/DCI
                 axis) or 'all' (every leg).
    ``error_feedback`` — carry the residual ``v - C(v)`` into the next
                 step's gradient (consumed by train/steps.py).
    ``reduce`` — psum-leg strategy: 'gather' exchanges quantized
                 payloads and sums after dequantization (wire bytes
                 actually shrink); 'qsum' shares a pmax scale and psums
                 exact int32 payloads (the legacy hier_int8 arithmetic).
                 'qsum' needs an integer dtype.
    """

    dtype: str = "int8"
    block: Optional[int] = 256
    scope: str = "cross-pod"
    error_feedback: bool = False
    reduce: str = "gather"

    def __post_init__(self):
        aliases = {"fp8-e4m3": "fp8", "fp8_e4m3": "fp8",
                   "cross-pod-only": "cross-pod"}
        object.__setattr__(self, "dtype",
                           aliases.get(self.dtype, self.dtype))
        object.__setattr__(self, "scope",
                           aliases.get(self.scope, self.scope))
        if self.dtype not in DTYPES:
            raise ValueError(f"compression dtype {self.dtype!r} not in "
                             f"{DTYPES}")
        if self.scope not in SCOPES:
            raise ValueError(f"compression scope {self.scope!r} not in "
                             f"{SCOPES}")
        if self.reduce not in REDUCES:
            raise ValueError(f"compression reduce {self.reduce!r} not in "
                             f"{REDUCES}")
        if self.reduce == "qsum" and self.dtype == "fp8":
            raise ValueError("reduce='qsum' needs an integer dtype "
                             "(int8/int4); fp8 payloads cannot be summed "
                             "exactly")
        if self.block is not None:
            if self.block <= 0:
                raise ValueError(f"block={self.block} must be positive")
            if self.dtype == "int4" and self.block % 2:
                raise ValueError("int4 packs two values per byte; block "
                                 "must be even")

    # -------------------------------------------------------------- labels
    def tag(self) -> str:
        s = self.dtype
        s += "[tensor]" if self.block is None else f"[b{self.block}]"
        if self.scope == "all":
            s += "+all"
        if self.reduce == "qsum":
            s += "+qsum"
        if self.error_feedback:
            s += "+ef"
        return s

    # ------------------------------------------------------ wire accounting
    def wire_bytes(self, n_elements: int) -> int:
        """Bytes one compressed ``n_elements``-float32 payload occupies on
        an in-scope leg: packed quantized values (padded to whole blocks)
        plus one float32 scale per block."""
        if n_elements <= 0:
            return 0
        B, nb = _row_block(self, n_elements)
        if self.dtype == "int4":
            payload = nb * (B // 2)
        else:
            payload = nb * B
        return payload + nb * 4

    def ratio(self, n_elements: int) -> float:
        """Wire-byte reduction vs float32 (>1 = smaller on the wire)."""
        wb = self.wire_bytes(n_elements)
        return (4.0 * n_elements / wb) if wb else 1.0


#: the spec that reproduces the pre-refactor ``hier_int8`` transport
#: bit-for-bit: per-tensor scale, pmax-shared, exact int32 cross-pod sum
LEGACY_INT8 = CompressionSpec(dtype="int8", block=None, scope="cross-pod",
                              reduce="qsum")


# ---------------------------------------------------------------------------
# quantize / dequantize (layout-aware per-block scales)
# ---------------------------------------------------------------------------


def _row_block(spec: CompressionSpec, m: int) -> Tuple[int, int]:
    """Static (block length B, blocks-per-row nb) for an m-element row."""
    if spec.block is None:
        B = m + (m % 2) if spec.dtype == "int4" else m
        B = max(B, 2 if spec.dtype == "int4" else 1)
    else:
        B = int(spec.block)
    nb = max(-(-m // B), 1)
    return B, nb


def container_dtype(spec: CompressionSpec):
    """The on-device dtype holding quantized values before wire packing."""
    if spec.dtype == "fp8":
        return jnp.float8_e4m3fn
    return jnp.uint8 if spec.dtype == "int4" else jnp.int8


def _pack_int4(k: Array) -> Array:
    """(r, B) int8 values in [-7, 7] -> (r, B//2) uint8 nibble pairs."""
    u = (k + 8).astype(jnp.uint8)                   # [1, 15]
    return (u[:, 1::2] << 4) | u[:, 0::2]


def _unpack_int4(p: Array) -> Array:
    """(r, B//2) uint8 nibble pairs -> (r, B) int8 values."""
    lo = (p & 0xF).astype(jnp.int8) - 8
    hi = (p >> 4).astype(jnp.int8) - 8
    return jnp.stack([lo, hi], axis=2).reshape(p.shape[0], 2 * p.shape[1])


def quantize_rows(rows: Array, spec: CompressionSpec):
    """Quantize each row independently (rows are self-contained payloads,
    e.g. per-destination alltoall blocks).

    ``rows`` (r, m) floating -> (container (r, nb * B'), scales (r, nb))
    where B' is the packed per-block width.  The per-block pipeline is
    the layout-aware formulation: reshape to (r*nb, B), amax scale per
    block, cast (and nibble-pack for int4)."""
    r, m = rows.shape
    B, nb = _row_block(spec, m)
    xb = rows.astype(jnp.float32)
    if nb * B != m:
        xb = jnp.pad(xb, ((0, 0), (0, nb * B - m)))
    xb = xb.reshape(r * nb, B)
    amax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / _QMAX[spec.dtype]
    if spec.dtype == "fp8":
        q = (xb / scale).astype(container_dtype(spec))
    else:
        qmax = _QMAX[spec.dtype]
        q = jnp.clip(jnp.round(xb / scale), -qmax, qmax).astype(jnp.int8)
        if spec.dtype == "int4":
            q = _pack_int4(q)
    return q.reshape(r, -1), scale.reshape(r, nb)


def dequantize_rows(q: Array, scales: Array, spec: CompressionSpec,
                    m: int, dtype) -> Array:
    """Inverse of :func:`quantize_rows`: -> (r, m) in ``dtype``."""
    r, nb = scales.shape
    qb = q.reshape(r * nb, -1)
    if spec.dtype == "int4":
        xb = _unpack_int4(qb).astype(jnp.float32)
    else:
        xb = qb.astype(jnp.float32)
    xb = xb * scales.reshape(r * nb, 1)
    return xb.reshape(r, -1)[:, :m].astype(dtype)


def qdq(x: Array, spec: CompressionSpec) -> Array:
    """The local lossy projection C(x) = dequantize(quantize(x)) — what
    the wire applies to a payload, and what error feedback corrects."""
    if not jnp.issubdtype(x.dtype, jnp.floating) or x.size == 0:
        return x
    q, s = quantize_rows(x.reshape(1, -1), spec)
    return dequantize_rows(q, s, spec, x.size, x.dtype).reshape(x.shape)


# ---------------------------------------------------------------------------
# wire containers: fp8 payloads travel as their raw bytes (uint8)
# ---------------------------------------------------------------------------


def _to_wire(q: Array) -> Array:
    if jnp.issubdtype(q.dtype, jnp.integer):
        return q
    return lax.bitcast_convert_type(q, jnp.uint8)


def _from_wire(w: Array, spec: CompressionSpec) -> Array:
    c = container_dtype(spec)
    return w if w.dtype == c else lax.bitcast_convert_type(w, c)


# ---------------------------------------------------------------------------
# shared-scale exact-sum reduction (the legacy hier_int8 arithmetic)
# ---------------------------------------------------------------------------


def _qsum_psum(x: Array, axis, spec: CompressionSpec) -> Array:
    """Quantized psum with a pmax-shared scale and an exact int32 sum.

    With ``spec.block is None`` this is op-for-op the pre-refactor
    ``hier_int8`` cross-pod leg (bitwise-identical results); per-block
    specs generalize the same arithmetic with (nb, 1) shared scales."""
    qmax = _QMAX[spec.dtype]
    if spec.block is None:
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / qmax
        scale = lax.pmax(scale, axis)
        q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
        return lax.psum(q, axis).astype(x.dtype) * scale
    flat = x.reshape(-1)
    m = flat.shape[0]
    B, nb = _row_block(spec, m)
    if nb * B != m:
        flat = jnp.pad(flat, (0, nb * B - m))
    xb = flat.reshape(nb, B).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xb), 1, keepdims=True), 1e-8) / qmax
    scale = lax.pmax(scale, axis)
    q = jnp.clip(jnp.round(xb / scale), -qmax, qmax).astype(jnp.int32)
    out = lax.psum(q, axis).astype(jnp.float32) * scale
    return out.reshape(-1)[:m].reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# the wire interception context
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def compressing(spec: CompressionSpec, axes):
    """Activate compression for the compat wire primitives over ``axes``
    for the duration of a transport op trace.  No-op when ``axes`` is
    empty (e.g. cross-pod scope on a mesh with no pod level)."""
    axes = tuple(axes)
    if not axes:
        yield
        return
    token = compat._COMPRESS.set(_WireCompressor(spec, frozenset(axes)))
    try:
        yield
    finally:
        compat._COMPRESS.reset(token)


@contextlib.contextmanager
def _plain():
    """Suspend interception while a handler issues its own wire calls —
    scales and already-quantized payloads must not be re-quantized."""
    token = compat._COMPRESS.set(None)
    try:
        yield
    finally:
        compat._COMPRESS.reset(token)


class _WireCompressor:
    """The object compat's primitives consult (see compat._COMPRESS).

    Each handler suspends the context, quantizes the payload, moves the
    (integer) wire bytes and per-block scales with the *same* compat
    primitive the algorithm asked for, and dequantizes on receipt — so
    scheduled rounds, emulated partial-manual rewrites, and native XLA
    collectives all carry compressed bytes unchanged."""

    def __init__(self, spec: CompressionSpec, axes: FrozenSet[str]):
        self.spec = spec
        self.axes = axes

    def _hits(self, axis) -> bool:
        names = axis if isinstance(axis, (tuple, list)) else (axis,)
        return any(a in self.axes for a in names)

    def applies(self, axis, x) -> bool:
        return (hasattr(x, "dtype")
                and jnp.issubdtype(x.dtype, jnp.floating)
                and getattr(x, "size", 0) > 0
                and self._hits(axis))

    # ------------------------------------------------------------ handlers
    def ppermute(self, x, axis, perm):
        with _plain():
            q, s = quantize_rows(x.reshape(1, -1), self.spec)
            wr = compat.ppermute(_to_wire(q), axis, perm)
            sr = compat.ppermute(s, axis, perm)
            out = dequantize_rows(_from_wire(wr, self.spec), sr, self.spec,
                                  x.size, x.dtype)
            return out.reshape(x.shape)

    def all_gather(self, x, axis):
        with _plain():
            k = lax.axis_size(axis)
            q, s = quantize_rows(x.reshape(1, -1), self.spec)
            w = _to_wire(q)
            wg = compat.all_gather_tiled(w.reshape(-1), axis)
            sg = compat.all_gather_tiled(s.reshape(-1), axis)
            rows = dequantize_rows(
                _from_wire(wg.reshape((k,) + w.shape[1:]), self.spec),
                sg.reshape(k, s.shape[1]), self.spec, x.size, x.dtype)
            # tiled concat semantics: per-rank payloads stack along dim 0
            return rows.reshape((k * x.shape[0],) + x.shape[1:])

    def psum(self, x, axis):
        names = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
        raw = tuple(a for a in names if a not in self.axes)
        comp = tuple(a for a in names if a in self.axes)
        y = x
        if raw:
            with _plain():
                y = compat.psum(y, raw if len(raw) > 1 else raw[0])
        for a in comp:
            y = self._reduce_axis(y, a)
        return y

    def _reduce_axis(self, x, a):
        with _plain():
            if self.spec.reduce == "qsum":
                return _qsum_psum(x, a, self.spec)
            # gather-reduce: every rank ships its quantized payload once
            # and sums after dequantization — bytes on the wire shrink by
            # the container ratio (qsum's int32 containers would not)
            k = lax.axis_size(a)
            q, s = quantize_rows(x.reshape(1, -1), self.spec)
            w = _to_wire(q)
            wg = compat.all_gather_tiled(w.reshape(-1), a)
            sg = compat.all_gather_tiled(s.reshape(-1), a)
            rows = dequantize_rows(
                _from_wire(wg.reshape((k,) + w.shape[1:]), self.spec),
                sg.reshape(k, s.shape[1]), self.spec, x.size, jnp.float32)
            return jnp.sum(rows, axis=0).reshape(x.shape).astype(x.dtype)

    def psum_scatter(self, x, axis):
        # compressed reduce + own-row slice: one definition of the op for
        # every schedule (documented simplification — the wire carries
        # whole payloads, like an allreduce)
        full = self.psum(x, axis)
        with _plain():
            me = lax.axis_index(axis)
            return lax.dynamic_slice(
                full, (me,) + (0,) * (x.ndim - 1), (1,) + x.shape[1:]
            ).reshape(x.shape[1:])

    def all_to_all(self, x, axis, dim=0):
        with _plain():
            n = lax.axis_size(axis)
            xm = jnp.moveaxis(x, dim, 0)
            rows = xm.reshape(n, -1)        # one self-contained row per peer
            m = rows.shape[1]
            q, s = quantize_rows(rows, self.spec)
            wr = compat.all_to_all_blocks(_to_wire(q), axis, 0)
            sr = compat.all_to_all_blocks(s, axis, 0)
            out = dequantize_rows(_from_wire(wr, self.spec), sr, self.spec,
                                  m, x.dtype)
            return jnp.moveaxis(out.reshape(xm.shape), 0, dim)


# ---------------------------------------------------------------------------
# the composing transport wrapper
# ---------------------------------------------------------------------------


#: the op surface the pre-refactor ``HierInt8Transport`` compressed:
#: reductions + alltoall cross-pod legs.  Its bcast/agg/allgather/
#: scatter were the plain tree schedules, and consumers (and the
#: transport-equivalence tests) observe those as EXACT — the alias
#: keeps that contract by limiting interception to these ops.
LEGACY_OPS = frozenset(
    {"allreduce", "reduce_scatter", "alltoall", "alltoallv"})


class CompressedTransport(Transport):
    """Compose a :class:`CompressionSpec` with ANY registered transport.

    No schedule is reimplemented: every op runs the inner transport's
    algorithm inside :func:`compressing`, so whatever wire primitives
    that algorithm issues over in-scope axes move quantized payloads.
    Integer payloads (MoE token routing) and out-of-scope legs pass
    through untouched.  ``ops`` limits which methods compress at all
    (``None`` = every op; the ``hier_int8`` alias passes
    :data:`LEGACY_OPS`).  Chaos wrapping (``faults.maybe_wrap``) nests
    *outside* this wrapper, so fault retries corrupt the float payload
    and the final clean attempt is the compressed exchange."""

    def __init__(self, inner: Transport, cspec: CompressionSpec,
                 ops: Optional[FrozenSet[str]] = None):
        super().__init__(inner.topo)
        self.inner = inner
        self.cspec = cspec
        self.ops = None if ops is None else frozenset(ops)
        self.name = f"{inner.name}+{cspec.tag()}"

    def _scope_axes(self) -> Tuple[str, ...]:
        if self.cspec.scope == "all":
            return tuple(self.topo.axes)
        return (self.topo.pod_axis,) if self.topo.pod_axis else ()

    def _cm(self, op: str):
        if self.ops is not None and op not in self.ops:
            return contextlib.nullcontext()
        return compressing(self.cspec, self._scope_axes())

    def allreduce(self, x):
        with self._cm("allreduce"):
            return self.inner.allreduce(x)

    def bcast(self, x, root: int = 0):
        with self._cm("bcast"):
            return self.inner.bcast(x, root)

    def agg(self, x, root: int = 0):
        with self._cm("agg"):
            return self.inner.agg(x, root)

    def allgather(self, x):
        with self._cm("allgather"):
            return self.inner.allgather(x)

    def scatter(self, x, root: int = 0):
        with self._cm("scatter"):
            return self.inner.scatter(x, root)

    def reduce_scatter(self, x):
        with self._cm("reduce_scatter"):
            return self.inner.reduce_scatter(x)

    def alltoall(self, x):
        with self._cm("alltoall"):
            return self.inner.alltoall(x)

    def alltoallv(self, x, counts):
        with self._cm("alltoallv"):
            return self.inner.alltoallv(x, counts)


# ---------------------------------------------------------------------------
# hier_int8: now an alias, not a transport class
# ---------------------------------------------------------------------------


@transports_lib.register_transport("hier_int8")
def _hier_int8_factory(topo) -> CompressedTransport:
    """``hier`` + :data:`LEGACY_INT8` under the historical name, so
    existing specs, benches, and the committed baseline keep working —
    and produce bitwise-identical results to the pre-refactor class."""
    t = CompressedTransport(transports_lib.get_transport("hier", topo),
                            LEGACY_INT8, ops=LEGACY_OPS)
    t.name = "hier_int8"
    return t
