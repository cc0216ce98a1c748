"""The stacked paged KV pool: ``paged_scatter`` and ``paged_gather``
address one layer of a (count, num_blocks, block_size, *row) pool by
layer index, with no per-layer slice, for both row forms (heads and
head width, or flat).  A write at layer i touches only layer i's leased
rows; a dropped token (padding, or an unleased logical
block) touches no layer at all, not even layer i+1's block 0, the row a
one-layer sentinel would name; an unleased block reads zeros."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import cache as cl

COUNT, NB, BS, H, DH = 3, 8, 4, 2, 3
# slot 0 leases blocks 5, 2 (logical 0, 1) and not logical 2; slot 1
# leases logical 0 only (block 0: layer i+1's block 0 is a live row)
BTAB = np.array([[5, 2, -1], [0, -1, -1]], np.int32)
WIDTHS = [1, 6]                    # a decode token, a chunk across blocks
ROWS = {"heads": (H, DH), "flat": (H * DH,)}


@pytest.fixture(params=sorted(ROWS))
def row(request):
    return ROWS[request.param]


def _pool(seed=0):
    """Distinct random contents in every layer, block and row, as
    (count, num_blocks, bs, H, dh); ``_stored`` gives the row form."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((COUNT, NB, BS, H, DH)).astype(np.float32)


def _stored(pool, row):
    return jnp.asarray(pool.reshape(pool.shape[:3] + row))


def _viewed(got):
    got = np.asarray(got)
    return got.reshape(got.shape[:3] + (H, DH))


def test_pool_row_keeps_heads_only_in_whole_lane_tiles():
    assert cl.pool_row(32, 128) == (32, 128)        # deepseek-7b
    assert cl.pool_row(8, 80) == (640,)             # h2o-danube-1.8b


def _tokens(width, seed=1):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((2, width, H, DH)).astype(np.float32)


def _expected_write(pool, layer, new, q_pos):
    """Reference: token at logical position p of slot b lands at
    (layer, btab[b, p // BS], p % BS) when p >= 0 and that block is
    leased; every other token is dropped."""
    out = pool.copy()
    for b in range(q_pos.shape[0]):
        for c in range(q_pos.shape[1]):
            p = q_pos[b, c]
            if p < 0:
                continue
            blk = BTAB[b, p // BS]
            if blk >= 0:
                out[layer, blk, p % BS] = new[b, c]
    return out


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("layer", range(COUNT))
def test_write_changes_only_its_layers_leased_rows(layer, width, row):
    pool, new = _pool(), _tokens(width)
    # slot 0 writes from position 2 on, over logical blocks 0-1 (and into
    # the unleased block 2 when the chunk is wide); slot 1 writes at 1
    q_pos = np.stack([2 + np.arange(width), 1 + np.arange(width)])
    q_pos[1, 3:] = -1                               # padding in slot 1
    q_pos = q_pos.astype(np.int32)
    got = _viewed(cl.paged_scatter(_stored(pool, row), jnp.asarray(BTAB),
                                   jnp.int32(layer), jnp.asarray(new),
                                   jnp.asarray(q_pos)))
    want = _expected_write(pool, layer, new, q_pos)
    np.testing.assert_array_equal(got, want)
    others = [i for i in range(COUNT) if i != layer]
    np.testing.assert_array_equal(got[others], pool[others])


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("layer", range(COUNT))
def test_dropped_tokens_leave_every_layer_unchanged(layer, width, row):
    pool, new = _pool(), _tokens(width)
    padding = np.full((2, width), -1, np.int32)
    # logical block 2 of slot 0 and blocks 1-2 of slot 1 are unleased
    unleased = np.stack([2 * BS + np.arange(width) % BS,
                         BS + np.arange(width)]).astype(np.int32)
    for q_pos in (padding, unleased):
        got = _viewed(cl.paged_scatter(
            _stored(pool, row), jnp.asarray(BTAB), jnp.int32(layer),
            jnp.asarray(new), jnp.asarray(q_pos)))
        np.testing.assert_array_equal(got, pool)
        if layer + 1 < COUNT:       # the next layer's block 0, row 0
            np.testing.assert_array_equal(got[layer + 1, 0, 0],
                                          pool[layer + 1, 0, 0])


@pytest.mark.parametrize("layer", range(COUNT))
def test_gather_reads_its_layer_and_zeros_where_unleased(layer, row):
    pool = _pool()
    got = np.asarray(cl.paged_gather(_stored(pool, row), jnp.asarray(BTAB),
                                     jnp.int32(layer)))
    assert got.shape == (2, BTAB.shape[1] * BS) + row
    got = got.reshape(got.shape[:2] + (H, DH))
    for b in range(2):
        for m, blk in enumerate(BTAB[b]):
            rows = got[b, m * BS:(m + 1) * BS]
            if blk >= 0:
                np.testing.assert_array_equal(rows, pool[layer, blk])
            else:
                assert not rows.any(), (b, m)
