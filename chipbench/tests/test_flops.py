"""The FLOP and byte counts against hand counts at a toy size."""
import itertools

import pytest

from chipbench.tests import tiny  # noqa: F401  (puts chipbench on the path)
from chipbench.lib import flops
from chipbench.peaks import PEAKS

# L=2 layers, D=4, 2 query heads and 1 KV head of 2, d_ff 3, vocab 5:
# per layer 4*2*2 (wq) + 2*4*1*2 (wk, wv) + 2*2*4 (wo) + 3*4*3 (ffn) = 84
TOY = {"num_layers": 2, "d_model": 4, "num_heads": 2, "num_kv_heads": 1,
       "head_dim": 2, "d_ff": 3, "vocab_size": 5, "sliding_window": 0}


def test_layer_weights():
    assert flops.layer_weights(TOY) == 84


def test_context_matches_the_sum():
    for s, n, w in itertools.product(range(12), range(9), range(1, 10)):
        want = sum(min(p + 1, w) for p in range(s, s + n))
        assert flops.context(s, n, w) == want


def test_prefill_row():
    # 3 tokens at positions 0..2: matmuls 3*2*2*84 = 1008, LM head
    # 2*4*5 = 40, attention over 1+2+3 keys: 4*L*H*dh*6 = 192
    assert flops.tick_flops(TOY, [(0, 3)]) == 1008 + 40 + 192


def test_decode_row_and_window():
    # one token at position 5 sees 6 keys, or 4 under a window of 4
    assert flops.tick_flops(TOY, [(5, 1)]) == 336 + 40 + 192
    assert flops.tick_flops(dict(TOY, sliding_window=4),
                            [(5, 1)]) == 336 + 40 + 128


def test_decode_bytes():
    # weights (2*(84 + 2*4) + 4 + 4*5) * 2 B = 416; the embedding row
    # 4*2 = 8; K/V per token 2*2*1*2*2 B = 16, for 6 keys read + 1 written
    assert flops.decode_bytes(TOY, [(5, 1)]) == 416 + 8 + 16 * 7


@pytest.mark.parametrize("op,want", [("send", 8), ("bcast", 6),
                                     ("scatter", 6), ("alltoall", 6),
                                     ("agg", 24), ("allreduce", 12)])
def test_exchange_bytes(op, want):
    assert flops.exchange_bytes(op, 8, 4) == want


def test_least_time_takes_the_larger_bound():
    p = PEAKS["TPU v5 lite"]
    assert flops.least_time(197e12, 0, p) == pytest.approx(1.0)
    assert flops.least_time(1.0, 819e9, p) == pytest.approx(1.0)
