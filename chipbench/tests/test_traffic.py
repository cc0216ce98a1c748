"""The traffic generator: deterministic under the seed, and the same
work for every seed."""
import numpy as np
import pytest

from chipbench.tests import tiny
from chipbench.lib import traffic
from chipbench.lib.harness import BENCH, read_json

CHAT = read_json(BENCH / "traffic" / "chat-azure-conv.json")
BACKLOG = read_json(BENCH / "traffic" / "longdoc-backlog.json")
BIG = 12345678901                  # seeds are wider than 32 bits


def _key(reqs):
    return [(r.prompt.tobytes(), r.max_new_tokens, r.arrival_s)
            for r in reqs]


def test_same_seed_same_requests():
    assert _key(traffic.generate(CHAT, BIG, 30, 32000)) == \
        _key(traffic.generate(CHAT, BIG, 30, 32000))


def _schedule(reqs):
    return [(len(r.prompt), r.max_new_tokens, r.arrival_s) for r in reqs]


@pytest.mark.parametrize("mix", [CHAT, BACKLOG], ids=["chat", "backlog"])
def test_seeds_differ_in_token_ids_only(mix):
    """Every seed gets the same lengths at the same times, in the order
    the mix's ``order_seed`` draws; only the token ids follow the seed."""
    a = traffic.generate(mix, BIG, 30, 1000)
    b = traffic.generate(mix, 3, 30, 1000)
    assert _schedule(a) == _schedule(b)
    assert _key(a) != _key(b)
    other = traffic.generate(dict(mix, order_seed=mix["order_seed"] + 1),
                             BIG, 30, 1000)
    assert _schedule(other) != _schedule(a)
    for field in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(field, other)) == sorted(map(field, a))


def test_poisson_mix_offers_its_rate():
    reqs = traffic.generate(CHAT, 7, 40, 32000)
    rate = CHAT["rate_per_s"]
    assert len(reqs) == int(np.ceil(rate * 40 * CHAT["overfill"]))
    due = sum(r.arrival_s < 40 for r in reqs)
    assert abs(due - rate * 40) <= 0.15 * rate * 40 + 2
    lens = [len(r.prompt) for r in reqs]
    assert min(lens) >= CHAT["prompt"]["min"]
    assert max(lens) <= CHAT["prompt"]["max"]
    assert abs(np.median(lens) - CHAT["prompt"]["median"]) \
        < 0.1 * CHAT["prompt"]["median"]


def test_backlog_is_due_at_once():
    reqs = traffic.generate(BACKLOG, 3, 30, 102400)
    assert len(reqs) == BACKLOG["requests"]
    assert all(r.arrival_s == 0 for r in reqs)
    assert all(BACKLOG["prompt"]["min"] <= len(r.prompt)
               <= BACKLOG["prompt"]["max"] for r in reqs)
    assert all(0 <= t < 102400 for r in reqs for t in r.prompt[:64])
    assert tiny.ROOT


def test_stratified_blocks_hold_one_value_of_each_stratum():
    rng = np.random.default_rng(5)
    v = np.arange(12)
    out = traffic.stratified(v, 4, rng)
    assert sorted(out) == list(v)
    for j in range(3):
        assert sorted(out[4 * j:4 * j + 4] // 3) == [0, 1, 2, 3]
