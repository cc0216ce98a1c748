"""Expert-parallel Mixture-of-Experts FFN, routed over the Communicator.

Experts are sharded over the ``model`` mesh axis.  All expert-parallel
communication goes through a model-axis-bound
:class:`~repro.comms.Communicator` — the same swappable, benchmarkable
transport stack (``native`` / ``tree`` / ``serial`` / ``hier`` /
``hier_int8``) that carries every other collective in the repo; there
are no direct ``lax.all_to_all`` calls here.  The transport is selected
by the ``comm`` argument (a registry name, a ``CommSpec``, or a
prebuilt ``Communicator``; ``ArchConfig.moe_comms`` / ``--moe-comms``
upstream), and the ``alltoall`` bench case family watches every option.

Two dispatch modes, trading exchange latency against replicated compute:

* ``scatter`` (train / chunked prefill): tokens are sharded over *all*
  mesh axes (batch over data/pod, sequence over model); each device
  routes its own tokens and exchanges them with the expert owners via
  two ``Communicator.alltoall``s (dispatch + combine).  Fixed
  per-destination capacity, overflow dropped (standard dropping MoE).
  The exchange bytes are explicit in the lowered HLO — exactly what the
  roofline collective term wants to see — and because the all-to-all is
  pure data movement, scatter-mode outputs are *bitwise identical*
  across transports (property-tested in tests/test_alltoall.py).

* ``replicated`` (decode): token counts are tiny (B tokens), so every
  model-rank routes the full local batch, computes only the assignments
  that land on its own experts, and partial results are combined with a
  single ``Communicator.allreduce`` over the model axis.  No all-to-all
  latency on the critical decode path, at the cost of every rank running
  the router on the full batch.

Compute is a batched einsum over the local expert block — FLOPs are
proportional to *active* parameters (x capacity factor), never to the
full expert count.  ``moe_ffn_reference`` is the pure-jnp dense oracle
used by tests.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.comms.communicator import CommSpec, Communicator
from repro.comms.compat import shard_map

Array = jax.Array


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def moe_init(key: Array, d_model: int, d_ff: int, num_experts: int,
             dtype=jnp.bfloat16) -> Dict[str, Array]:
    from repro.models.layers import dense_init
    k1, k2, k3, k4 = jax.random.split(key, 4)
    E, D, F = num_experts, d_model, d_ff
    return {
        "wr": dense_init(k1, (D, E), jnp.float32),
        "we1": dense_init(k2, (E, D, F), dtype),
        "we3": dense_init(k3, (E, D, F), dtype),
        "we2": dense_init(k4, (E, F, D), dtype, scale=F ** -0.5),
    }


def _route(x: Array, wr: Array, top_k: int) -> Tuple[Array, Array, Array]:
    """Router.  x: (T, D) -> (weights (T,k) f32, eids (T,k) i32, probs)."""
    logits = x.astype(jnp.float32) @ wr.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, eids = lax.top_k(probs, top_k)
    weights = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    return weights, eids, probs


def _positions_within(dest: Array, n_dest: int) -> Array:
    """Rank of each element among elements with the same destination.
    dest: (A,) int32 in [0, n_dest)."""
    oh = (dest[:, None] == jnp.arange(n_dest, dtype=dest.dtype)[None, :])
    pos = jnp.cumsum(oh.astype(jnp.int32), axis=0) - 1
    return jnp.take_along_axis(pos, dest[:, None].astype(jnp.int32), axis=1)[:, 0]


def _aux_loss(probs: Array, eids: Array, num_experts: int) -> Array:
    """Switch-style load-balancing loss (local shard contribution)."""
    T = probs.shape[0]
    top1 = eids[:, 0]
    frac = jnp.zeros((num_experts,), jnp.float32).at[top1].add(1.0) / T
    mean_prob = probs.mean(0)
    return num_experts * jnp.sum(frac * mean_prob)


def _expert_compute(buf: Array, w1: Array, w3: Array, w2: Array) -> Array:
    """buf: (E_loc, Ce, D) -> (E_loc, Ce, D) via per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1)) \
        * jnp.einsum("ecd,edf->ecf", buf, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _gather_fsdp(w: Array, axis: int, fsdp_axes: Sequence[str],
                 gather_dtype: str = "bf16") -> Array:
    """All-gather FSDP-sharded expert weights at use.

    ``gather_dtype='int8'`` quantizes the local block (per-channel scales
    along the gathered axis) before the gather — halves the dominant
    collective bytes of MoE training; dequantized blockwise after."""
    if not fsdp_axes:
        return w
    if gather_dtype != "int8":
        for a in fsdp_axes:
            w = lax.all_gather(w, a, axis=axis, tiled=True)
        return w
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    blk = w.shape[axis]
    nsh = 1
    for a in fsdp_axes:
        q = lax.all_gather(q, a, axis=axis, tiled=True)
        scale = lax.all_gather(scale, a, axis=axis, tiled=True)
        nsh *= lax.axis_size(a)
    shp = q.shape
    split = shp[:axis] + (nsh, blk) + shp[axis + 1:]
    qs = q.reshape(split).astype(jnp.bfloat16)
    ss = scale.reshape(shp[:axis] + (nsh, 1) + shp[axis + 1:]
                       ).astype(jnp.bfloat16)
    return (qs * ss).reshape(shp)


# ---------------------------------------------------------------------------
# scatter mode (train / prefill)
# ---------------------------------------------------------------------------

def _moe_scatter_local(x: Array, wr: Array, w1: Array, w3: Array, w2: Array,
                       *, top_k: int, num_experts: int, model_size: int,
                       capacity_factor: float,
                       fsdp_axes: Sequence[str],
                       model_axis: str, comm: Communicator,
                       gather_dtype: str = "bf16") -> Tuple[Array, Array]:
    """Per-device body (inside shard_map).  x: (Tl, D) local tokens."""
    Tl, D = x.shape
    M, E = model_size, num_experts
    E_loc = E // M
    w1 = _gather_fsdp(w1, 2, fsdp_axes, gather_dtype)
    w3 = _gather_fsdp(w3, 2, fsdp_axes, gather_dtype)
    w2 = _gather_fsdp(w2, 1, fsdp_axes, gather_dtype)

    weights, eids, probs = _route(x, wr, top_k)
    aux = _aux_loss(probs, eids, E)

    A = Tl * top_k
    eids_f = eids.reshape(A)
    w_f = weights.reshape(A)
    tok_f = jnp.arange(A, dtype=jnp.int32) // top_k
    dst = eids_f // E_loc
    leid = eids_f % E_loc

    C = _round_up(max(int(math.ceil(A / M * capacity_factor)), 8), 8)
    pos = _positions_within(dst, M)
    keep = pos < C
    slot = jnp.where(keep, dst * C + pos, M * C)

    send_x = jnp.zeros((M * C, D), x.dtype).at[slot].set(
        x[tok_f], mode="drop")
    send_leid = jnp.full((M * C,), -1, jnp.int32).at[slot].set(
        leid, mode="drop")

    # dispatch: per-destination blocks -> expert owners, through the
    # Communicator's swappable alltoall (pure data movement — outputs
    # are transport-invariant bit-for-bit)
    recv_x, recv_leid = comm.alltoall((send_x, send_leid))

    R = M * C
    Ce = _round_up(max(int(math.ceil(R / E_loc * capacity_factor)), 8), 8)
    valid = recv_leid >= 0
    pos2 = _positions_within(jnp.where(valid, recv_leid, 0), E_loc)
    keep2 = valid & (pos2 < Ce)
    slot2 = jnp.where(keep2, recv_leid * Ce + pos2, E_loc * Ce)

    ebuf = jnp.zeros((E_loc * Ce, D), x.dtype).at[slot2].set(
        recv_x, mode="drop")
    y = _expert_compute(ebuf.reshape(E_loc, Ce, D), w1, w3, w2)
    y = y.reshape(E_loc * Ce, D)

    out_r = jnp.where(keep2[:, None],
                      jnp.take(y, jnp.minimum(slot2, E_loc * Ce - 1), axis=0),
                      0).astype(x.dtype)
    back = comm.alltoall(out_r)          # combine: results -> token owners

    y_a = jnp.where(keep[:, None],
                    jnp.take(back, jnp.minimum(slot, M * C - 1), axis=0),
                    0)
    y_tok = jnp.sum(y_a.reshape(Tl, top_k, D)
                    * w_f.reshape(Tl, top_k, 1).astype(x.dtype), axis=1)
    return y_tok, aux


# ---------------------------------------------------------------------------
# replicated mode (decode)
# ---------------------------------------------------------------------------

def _moe_replicated_local(x: Array, wr: Array, w1: Array, w3: Array,
                          w2: Array, *, top_k: int, num_experts: int,
                          model_size: int, fsdp_axes: Sequence[str],
                          model_axis: str, comm: Communicator,
                          gather_dtype: str = "bf16") -> Tuple[Array, Array]:
    """Decode path: x (Tl, D) replicated over the model axis; each rank
    computes only assignments hitting its local experts; the
    Communicator's allreduce combines the partial results."""
    Tl, D = x.shape
    M, E = model_size, num_experts
    E_loc = E // M
    my = lax.axis_index(model_axis)
    w1 = _gather_fsdp(w1, 2, fsdp_axes, gather_dtype)
    w3 = _gather_fsdp(w3, 2, fsdp_axes, gather_dtype)
    w2 = _gather_fsdp(w2, 1, fsdp_axes, gather_dtype)

    weights, eids, _ = _route(x, wr, top_k)
    A = Tl * top_k
    eids_f = eids.reshape(A)
    w_f = weights.reshape(A)
    mine = (eids_f // E_loc) == my
    leid = eids_f % E_loc

    Ce = _round_up(max(A, 8), 8)  # no drops on the decode path
    pos = _positions_within(jnp.where(mine, leid, 0), E_loc)
    slot = jnp.where(mine, leid * Ce + pos, E_loc * Ce)
    tok_f = jnp.arange(A, dtype=jnp.int32) // top_k

    ebuf = jnp.zeros((E_loc * Ce, D), x.dtype).at[slot].set(
        x[tok_f], mode="drop")
    y = _expert_compute(ebuf.reshape(E_loc, Ce, D), w1, w3, w2)
    y = y.reshape(E_loc * Ce, D)

    y_a = jnp.where(mine[:, None],
                    jnp.take(y, jnp.minimum(slot, E_loc * Ce - 1), axis=0), 0)
    y_tok = jnp.sum(y_a.reshape(Tl, top_k, D)
                    * w_f.reshape(Tl, top_k, 1).astype(x.dtype), axis=1)
    y_tok = comm.allreduce(y_tok)
    return y_tok, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def moe_ffn(params: Dict[str, Array], x: Array, *, top_k: int,
            num_experts: int, capacity_factor: float, mesh: Mesh,
            batch_axes: Tuple[str, ...], model_axis: str = "model",
            fsdp_axes: Tuple[str, ...] = (), mode: str = "scatter",
            comm: Union[str, CommSpec, Communicator, None] = None,
            gather_dtype: str = "bf16") -> Tuple[Array, Array]:
    """MoE FFN.  x: (B, T, D) -> (B, T, D), aux-loss scalar.

    ``comm`` picks the transport carrying the expert exchange: a
    registry name ('native', 'tree', ...), a ``CommSpec``, or a prebuilt
    model-axis ``Communicator``; None means 'native'.  In scatter mode
    the T axis must be divisible by the model-axis size.
    """
    B, T, D = x.shape
    M = mesh.shape[model_axis]
    if not isinstance(comm, Communicator):
        comm = Communicator.for_mesh(mesh, comm, axes=(model_axis,))
    expert_spec1 = P(model_axis, None, fsdp_axes if fsdp_axes else None)
    expert_spec2 = P(model_axis, fsdp_axes if fsdp_axes else None, None)

    if mode == "scatter":
        x_spec = P(batch_axes, model_axis, None)
        body = functools.partial(
            _moe_scatter_local, top_k=top_k, num_experts=num_experts,
            model_size=M, capacity_factor=capacity_factor,
            fsdp_axes=fsdp_axes, model_axis=model_axis, comm=comm,
            gather_dtype=gather_dtype)
    else:
        x_spec = P(batch_axes, None, None)
        body = functools.partial(
            _moe_replicated_local, top_k=top_k, num_experts=num_experts,
            model_size=M, fsdp_axes=fsdp_axes, model_axis=model_axis,
            comm=comm, gather_dtype=gather_dtype)

    def local(x3, wr, w1, w3_, w2):
        b, t, d = x3.shape
        y, aux = body(x3.reshape(b * t, d), wr, w1, w3_, w2)
        # aux: average over every device that computed a distinct shard
        aux = lax.pmean(aux, batch_axes) if batch_axes else aux
        if mode == "scatter":
            aux = lax.pmean(aux, model_axis)
        return y.reshape(b, t, d), aux

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None), expert_spec1, expert_spec1,
                  expert_spec2),
        out_specs=(x_spec, P()))
    return fn(x, params["wr"], params["we1"], params["we3"], params["we2"])


# ---------------------------------------------------------------------------
# dense oracle (tests)
# ---------------------------------------------------------------------------

def moe_ffn_reference(params: Dict[str, Array], x: Array, *, top_k: int,
                      num_experts: int) -> Tuple[Array, Array]:
    """Dense-masked reference: every expert on every token, masked combine.
    O(E) FLOPs — only for tiny test shapes."""
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    weights, eids, probs = _route(xf, params["wr"], top_k)
    aux = _aux_loss(probs, eids, num_experts)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, params["we1"])) \
        * jnp.einsum("td,edf->etf", xf, params["we3"])
    y_all = jnp.einsum("etf,efd->etd", h, params["we2"])   # (E, T, D)
    comb = jnp.zeros((B * T, num_experts), jnp.float32)
    comb = jax.vmap(lambda c, e, w: c.at[e].add(w))(comb, eids, weights)
    y = jnp.einsum("te,etd->td", comb.astype(x.dtype), y_all)
    return y.reshape(B, T, D), aux
