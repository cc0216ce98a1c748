"""KV / recurrent-state cache machinery.

A *cache entry* serves one stack of ``count`` identical layers (the scan
group).  KV entries come in two storage layouts:

* **ring** — a per-slot buffer of length ``cache_len`` = min(max_len,
  window): sliding-window layers keep only their window, global layers
  the full sequence.
* **paged** — a *shared* physical pool of fixed-size blocks
  ((count, num_blocks, block_size, *row); ``pool_row`` gives a token's
  row, (kv_heads, head_dim) or flat) plus a per-slot block table
  ``btab`` (B, max_blocks) mapping logical block -> physical block (-1 =
  unleased).  Slots lease blocks on demand (see repro/serve/pool.py)
  instead of reserving ``max_len`` rings up front; the attention path
  gathers/scatters through the table.  Used for full-length entries
  where the dense reservation is the memory cost worth paging.  The
  pool stays stacked over the layers: the layer scan carries it whole
  and each layer reads and writes its own blocks by layer index
  (``paged_gather``/``paged_scatter``), so no layer's pool is sliced
  out, restacked or copied.  The drop/fill sentinel row lies beyond the
  whole stacked pool.

Slot positions are tracked explicitly in ``pos`` (shape (B, L), -1 =
empty) so attention masks are always derived from true token positions —
ring wraparound, chunked prefill, paging and per-sequence decode offsets
all fall out of one code path.

Writes are masked per-token scatters (``scatter_ring``): tokens with
``q_pos < 0`` are dropped entirely, which lets a serving batch mix
prefill chunks, single decode tokens and idle slots in one dispatch
without clobbering live cache lines.

Update discipline (see repro/models/blocks.py):
  * chunk extend (C > 1): attend over [old cache ++ chunk], then write the
    chunk ("attend-then-update" — never clobbers keys the chunk still
    needs);
  * decode (C == 1): write first, then attend over the cache only
    ("update-then-attend" — avoids a full cache copy per token; safe
    because the overwritten slot is exactly window positions old).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PageSpec:
    """Paged-pool geometry: ``num_blocks`` physical blocks of
    ``block_size`` tokens shared by all slots of an entry."""

    block_size: int
    num_blocks: int

    def logical_blocks(self, max_len: int) -> int:
        return -(-max_len // self.block_size)        # ceil

    def logical_len(self, max_len: int) -> int:
        return self.logical_blocks(max_len) * self.block_size


def kv_entry(count: int, batch: int, cache_len: int, kv_heads: int,
             head_dim: int, dtype=jnp.bfloat16) -> Dict[str, Array]:
    return {
        "k": jnp.zeros((count, batch, cache_len, kv_heads, head_dim), dtype),
        "v": jnp.zeros((count, batch, cache_len, kv_heads, head_dim), dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def kv_entry_specs(count, batch, cache_len, kv_heads, head_dim,
                   dtype=jnp.bfloat16):
    return {
        "k": jax.ShapeDtypeStruct((count, batch, cache_len, kv_heads, head_dim), dtype),
        "v": jax.ShapeDtypeStruct((count, batch, cache_len, kv_heads, head_dim), dtype),
        "pos": jax.ShapeDtypeStruct((batch, cache_len), jnp.int32),
    }


def ring_indices(q_pos: Array, W: int) -> Array:
    """Per-token ring write index for chunk positions ``q_pos`` (B, C):
    ``p % W`` for tokens that survive (valid and within the chunk's last
    ``W`` positions — older ones would be overwritten by the same chunk),
    ``W`` (out of range => dropped by ``mode='drop'``) otherwise."""
    valid = q_pos >= 0
    last = jnp.max(jnp.where(valid, q_pos, -1), axis=1, keepdims=True)
    keep = valid & (q_pos > last - W)
    return jnp.where(keep, q_pos % W, W)


def scatter_ring(buf: Array, new: Array, q_pos: Array) -> Array:
    """Masked per-token scatter of ``new`` (B, C, ...) into ring ``buf``
    (B, W, ...): token at absolute position p lands at slot ``p % W``;
    tokens with ``q_pos < 0`` (padding / idle slots) are dropped.  Unlike
    a contiguous dynamic-update-slice this is safe for ragged serving
    batches where only some batch rows carry real tokens."""
    idx = ring_indices(q_pos, buf.shape[1])

    def scat(b, i, n):
        return b.at[i].set(n, mode="drop")

    return jax.vmap(scat)(buf, idx, new)


def update_kv(entry_k: Array, entry_v: Array, pos: Array,
              new_k: Array, new_v: Array, q_pos: Array
              ) -> Tuple[Array, Array, Array]:
    """Write a chunk into one layer's ring.

    entry_k/v: (B, W, H, dh); pos: (B, W); new_k/v: (B, C, H, dh);
    q_pos: (B, C) absolute positions of the chunk tokens (-1 = padding,
    dropped).
    """
    k2 = scatter_ring(entry_k, new_k, q_pos)
    v2 = scatter_ring(entry_v, new_v, q_pos)
    pos2 = scatter_ring(pos, q_pos, q_pos)
    return k2, v2, pos2


# --- paged entries ---------------------------------------------------------
#
# A paged entry's pool stays stacked, (count, num_blocks, bs, *row), and
# is read and written by layer index: the layer scan carries the whole
# pool and updates it in place, never slicing out one layer's blocks.
# Both ops address rows of the flattened stacked pool, row
# ``(layer * num_blocks + block) * bs + offset``; the row for a dropped
# write or an unleased read is ``count * num_blocks * bs``, beyond the
# whole stacked pool, so it can never name a row of another layer.

#: lanes of the TPU's minor tile: an array's minor dimension is laid out
#: in whole units of 128 elements
LANES = 128


def pool_row(kv_heads: int, head_dim: int) -> Tuple[int, ...]:
    """Trailing shape of one token's row in a paged pool.

    (kv_heads, head_dim) when head_dim fills whole lane tiles; else the
    flat kv_heads * head_dim.  A minor dimension that pads its tile
    makes the device lay the pool out with the block axis minor, and the
    layer scan would then relayout the whole pool on every tick."""
    if head_dim % LANES == 0:
        return (kv_heads, head_dim)
    return (kv_heads * head_dim,)


def _flat_pool(pool: Array) -> Array:
    """(count, num_blocks, bs, *row) stacked pool -> (count * num_blocks *
    bs, *row) rows; merges major axes only, so no data moves."""
    return pool.reshape((-1,) + pool.shape[3:])


def paged_gather(pool: Array, btab: Array, layer: Array) -> Array:
    """Materialize layer ``layer``'s logical per-slot view of a paged pool.

    pool: (count, num_blocks, bs, *row) stacked physical pool; btab:
    (B, M) block table; layer: scalar layer index.  Returns (B, M * bs,
    *row) where logical token position p of slot b lives at index p;
    unleased blocks read as zeros (their ``pos`` entries are -1, so
    attention masks them out).  One gather from the stacked pool.
    """
    with jax.named_scope("paged_gather"):
        nb, bs = pool.shape[1:3]
        flat = _flat_pool(pool)
        base = jnp.where(btab >= 0, (layer * nb + btab) * bs,
                         flat.shape[0])                 # past the pool => 0
        idx = base[:, :, None] + jnp.arange(bs, dtype=btab.dtype)[None, None]
        idx = idx.reshape(btab.shape[0], -1)
        return jnp.take(flat, idx, axis=0, mode="fill", fill_value=0)


def paged_scatter(pool: Array, btab: Array, layer: Array, new: Array,
                  q_pos: Array) -> Array:
    """Write chunk tokens into layer ``layer`` of the stacked pool through
    the block table, with one scatter on the whole pool.

    pool: (count, num_blocks, bs, *row); btab: (B, M); layer: scalar;
    new: (B, C, H, dh), written as rows of ``pool``'s row shape; q_pos:
    (B, C) logical positions (-1 = padding).
    Tokens whose position is invalid or whose logical block is unleased
    are dropped: they land in no layer and in no other slot's blocks.
    """
    with jax.named_scope("paged_scatter"):
        nb, bs = pool.shape[1:3]
        flat = _flat_pool(pool)
        lb = jnp.where(q_pos >= 0, q_pos // bs, 0)
        blk = jnp.take_along_axis(btab, lb, axis=1)           # (B, C)
        row = jnp.where((q_pos >= 0) & (blk >= 0),
                        (layer * nb + blk) * bs + q_pos % bs,
                        flat.shape[0])                  # past the pool => drop
        flat = flat.at[row.reshape(-1)].set(
            new.reshape((-1,) + pool.shape[3:]), mode="drop")
        return flat.reshape(pool.shape)


def cache_len_for(window: int, max_len: int) -> int:
    from repro.configs.base import GLOBAL_WINDOW
    if window >= GLOBAL_WINDOW or window <= 0:
        return max_len
    return min(window, max_len)


# --- recurrent-state entries (xLSTM / Mamba-style) -------------------------

def mlstm_entry(count, batch, heads, dh, dtype=jnp.float32):
    return {
        "C": jnp.zeros((count, batch, heads, dh, dh), dtype),
        "n": jnp.zeros((count, batch, heads, dh), dtype),
        "m": jnp.full((count, batch, heads), -jnp.inf, dtype),
    }


def slstm_entry(count, batch, heads, dh, dtype=jnp.float32):
    return {
        "c": jnp.zeros((count, batch, heads, dh), dtype),
        "n": jnp.zeros((count, batch, heads, dh), dtype),
        "h": jnp.zeros((count, batch, heads, dh), dtype),
        "m": jnp.full((count, batch, heads, dh), -jnp.inf, dtype),
    }


def ssm_entry(count, batch, d_inner, state, conv_taps=3, dtype=jnp.float32):
    return {
        "h": jnp.zeros((count, batch, d_inner, state), dtype),
        "conv": jnp.zeros((count, batch, conv_taps, d_inner), dtype),
    }
