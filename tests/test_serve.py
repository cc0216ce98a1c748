"""Serving subsystem: block-pool accounting, scheduler tick planning,
and engine end-to-end behaviour — paged == dense bit-for-bit at
temperature 0, staggered admission == solo greedy, truncation is
reported (never silent), EOS completion, load guards, the legacy path
for recurrent architectures, and the multi-rank drain barrier (in a
2-device subprocess).

Single-device engine tests run in-process and share module-scoped
engines so each dispatch width compiles once.
"""
import numpy as np
import pytest

from tests._subproc import run_py

# ------------------------------------------------------------- pool (pure)


def test_block_pool_accounting():
    from repro.serve import BlockPool, PoolExhausted

    pool = BlockPool(num_blocks=8, block_size=4, slots=3, max_len=16)
    assert pool.max_blocks_per_slot == 4
    assert pool.blocks_for(0) == 0 and pool.blocks_for(1) == 1
    assert pool.blocks_for(4) == 1 and pool.blocks_for(5) == 2

    pool.reserve(0, 9)                    # worst case: 3 blocks committed
    assert pool.committed == 3 and pool.used_blocks == 0
    with pytest.raises(ValueError):
        pool.reserve(0, 4)                # double-reserve is a bug

    pool.ensure(0, 5)                     # lease on demand: 2 of 3
    assert pool.used_blocks == 2 and pool.high_water == 2 and pool.dirty
    assert (pool.table[0, :2] >= 0).all() and pool.table[0, 2] == -1
    with pytest.raises(PoolExhausted):
        pool.ensure(0, 13)                # beyond the slot's commitment

    pool.reserve(1, 16)                   # 3 + 4 = 7 of 8
    assert not pool.can_reserve(16) and pool.can_reserve(4)
    with pytest.raises(PoolExhausted):
        pool.reserve(2, 16)               # would overcommit the pool

    pool.release(0)
    assert pool.committed == 4 and pool.used_blocks == 0
    assert (pool.table[0] == -1).all()
    assert pool.high_water == 2           # peak footprint is sticky

    with pytest.raises(ValueError):
        BlockPool(num_blocks=0, block_size=4, slots=1, max_len=16)


# -------------------------------------------------------- scheduler (pure)


def test_scheduler_conservative_ticks():
    from repro.serve import Scheduler

    sched = Scheduler(slots=2, chunk=4)
    st = sched.assign(0, rid=7, prompt=np.arange(6), cap=2,
                      temperature=0.0, eos_id=None)

    p1 = sched.plan()                     # first prefill chunk, full width
    assert p1.kind == "chunk" and p1.width == 4
    assert list(p1.lengths) == [4, 0] and list(p1.starts) == [0, 0]
    assert not p1.samples and not p1.use_next.any() and st.fed == 4

    p2 = sched.plan()                     # tail chunk completes -> samples
    assert list(p2.lengths) == [2, 0] and p2.starts[0] == 4
    assert p2.samples == [(0, st.epoch, 0)] and st.sampled == 1

    p3 = sched.plan()                     # decode ticks are width 1
    assert p3.kind == "decode" and p3.width == 1
    assert p3.use_next[0] and p3.samples == [(0, st.epoch, 1)]

    assert sched.plan() is None           # cap=2 dispatched; nothing left
    assert not sched.has_work()

    with pytest.raises(ValueError):
        Scheduler(slots=1, chunk=4, policy="nope")


def test_scheduler_mixed_packs_decode_into_chunks():
    from repro.serve import Scheduler

    sched = Scheduler(slots=2, chunk=4, policy="mixed")
    s0 = sched.assign(0, rid=0, prompt=np.arange(2), cap=3,
                      temperature=0.0, eos_id=None)
    sched.plan()                          # slot 0 finishes prefill
    assert s0.decode_ready
    s1 = sched.assign(1, rid=1, prompt=np.arange(6), cap=1,
                      temperature=0.0, eos_id=None)
    p = sched.plan()                      # decode row rides the chunk tick
    assert p.kind == "chunk"
    assert list(p.lengths) == [1, 4] and list(p.use_next) == [True, False]
    assert (0, s0.epoch, 1) in p.samples and s1.prefilling


# ------------------------------------------------- engine (1 device, jax)


@pytest.fixture(scope="module")
def stack():
    import jax
    from repro.configs.base import get_config, reduced
    from repro.launch.mesh import mesh_for_devices
    from repro.models.model import Model

    cfg = reduced(get_config("gemma3-4b"))
    mesh = mesh_for_devices(1)
    params = Model(cfg, mesh).init(jax.random.PRNGKey(0))
    return cfg, mesh, params


def _engine(stack, **kw):
    from repro.serve import Engine

    cfg, mesh, params = stack
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("block_size", 8)
    eng = Engine(cfg, mesh, **kw)
    eng.load(params)
    return eng


@pytest.fixture(scope="module")
def paged_engine(stack):
    return _engine(stack, cache_mode="paged")


@pytest.fixture(scope="module")
def dense_engine(stack):
    return _engine(stack, cache_mode="dense")


@pytest.fixture(scope="module")
def solo_engine(stack):
    return _engine(stack, slots=1, cache_mode="paged")


def _reqs(stack, lens=(5, 9, 3, 7), new=4, **kw):
    from repro.serve import Request

    cfg = stack[0]
    rng = np.random.default_rng(1)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n),
                    max_new_tokens=new, **kw)
            for i, n in enumerate(lens)]


def test_paged_matches_dense_bitwise_and_memory(stack, paged_engine,
                                                dense_engine):
    res_p = paged_engine.run_to_completion(_reqs(stack))
    res_d = dense_engine.run_to_completion(_reqs(stack))
    assert not res_p.truncated and not res_d.truncated
    assert sorted(res_p) == sorted(res_d) == [0, 1, 2, 3]
    for rid in res_p:                     # greedy: bit-for-bit identical
        assert res_p[rid] == res_d[rid] and len(res_p[rid]) == 4
        m = res_p.metrics[rid]
        assert m["ttft_s"] is not None and m["tokens"] == 4
        assert m["done_s"] >= m["ttft_s"] >= 0.0

    # paged footprint is proportional to live tokens, not slots*max_len
    pool = paged_engine.pool
    assert pool.used_blocks == 0          # drained
    assert 0 < pool.high_water * pool.block_size < \
        paged_engine.slots * paged_engine.max_len
    assert dense_engine.pool is None


def test_staggered_admission_matches_solo_greedy(stack, paged_engine,
                                                 solo_engine):
    solo = {}
    for r in _reqs(stack, lens=(5, 9, 3)):
        solo[r.rid] = solo_engine.run_to_completion([r])[r.rid]

    reqs = _reqs(stack, lens=(5, 9, 3))
    assert paged_engine.admit(reqs[0])
    for _ in range(2):
        paged_engine.step()               # r0 mid-flight when r1 arrives
    assert paged_engine.admit(reqs[1])
    paged_engine.step()
    assert paged_engine.admit(reqs[2])
    while paged_engine.sched.has_work():
        paged_engine.step()
    for r in reqs:
        assert r.out_tokens == solo[r.rid], r.rid


def test_eos_stops_generation(stack, paged_engine, solo_engine):
    base = solo_engine.run_to_completion(_reqs(stack, lens=(6,), new=6))[0]
    k = base.index(base[len(base) // 2])  # first occurrence of a mid token
    res = paged_engine.run_to_completion(
        _reqs(stack, lens=(6,), new=6, eos_id=base[k]))
    assert res[0] == base[:k + 1]
    assert res.metrics[0]["tokens"] == k + 1


def test_sampling_is_seeded_and_batched(stack, paged_engine):
    import jax

    def run(seed):
        paged_engine.key = jax.random.PRNGKey(seed)
        res = paged_engine.run_to_completion(
            _reqs(stack, lens=(5, 9), new=6, temperature=0.8))
        return [res[0], res[1]]

    a, b, c = run(3), run(3), run(4)
    assert a == b                         # same key -> same draws
    assert a != c                         # different key -> different draws
    assert all(len(t) == 6 for t in a)


def test_zero_cap_and_guards(stack, paged_engine):
    from repro.serve import Engine, Request

    cfg, mesh, _ = stack
    # prompt fills max_len minus nothing -> no generation budget
    res = paged_engine.run_to_completion(_reqs(stack, lens=(4,), new=0))
    assert res[0] == [] and res.metrics[0]["tokens"] == 0

    with pytest.raises(ValueError):       # prompt + 1 must fit max_len
        paged_engine.run_to_completion(_reqs(stack, lens=(32,)))

    cold = Engine(cfg, mesh, slots=1, max_len=32)
    with pytest.raises(RuntimeError, match="load"):
        cold.admit(Request(rid=0, prompt=np.arange(3)))
    with pytest.raises(RuntimeError, match="load"):
        cold.step()
    with pytest.raises(RuntimeError, match="load"):
        cold.run_to_completion([])


def test_never_admittable_request_rejected_up_front(stack):
    # pool smaller than one request's worst case: fail fast, don't spin
    eng = _engine(stack, slots=1, cache_mode="paged", num_blocks=1)
    with pytest.raises(ValueError, match="blocks"):
        eng.run_to_completion(_reqs(stack, lens=(9,)))


def test_truncation_is_reported_not_silent(stack, paged_engine):
    reqs = _reqs(stack, lens=(5, 9), new=6)
    res = paged_engine.run_to_completion(reqs, max_steps=2)
    assert res.truncated
    assert set(res.unfinished) == {0, 1} and not res
    while paged_engine.sched.has_work():  # drain for subsequent tests
        paged_engine.step()
    assert paged_engine.pool.used_blocks == 0


def test_two_paged_entries_share_one_undonated_block_table():
    # local windows >= max_len: both sub-blocks of the local/global group
    # are paged, and every tick donates the cache with both entries in it
    import jax
    from repro.configs.base import get_config, reduced
    from repro.launch.mesh import mesh_for_devices
    from repro.models.model import Model

    cfg = reduced(get_config("gemma3-4b"), sliding_window=32)
    mesh = mesh_for_devices(1)
    stack2 = (cfg, mesh, Model(cfg, mesh).init(jax.random.PRNGKey(0)))
    paged = _engine(stack2, cache_mode="paged")
    assert len(paged.paged_entries) >= 2
    assert all("btab" not in ent for ent in paged.cache.values())
    res_p = paged.run_to_completion(_reqs(stack2))
    res_d = _engine(stack2, cache_mode="dense").run_to_completion(
        _reqs(stack2))
    assert not res_p.truncated and sorted(res_p) == [0, 1, 2, 3]
    assert all(res_p[rid] == res_d[rid] for rid in res_p)


def test_upload_copies_host_buffer():
    # a 64-byte-aligned numpy buffer is the case the CPU backend aliases
    import jax.numpy as jnp
    from repro.serve.engine import _upload

    raw = np.zeros(8 + 64, np.uint8)
    off = (-raw.ctypes.data) % 64
    mask = raw[off:off + 8].view(bool)
    dev = _upload(mask)
    mask[:] = True                        # host edit after the upload
    assert not np.asarray(dev).any()
    assert isinstance(dev, jnp.ndarray)


def test_legacy_path_serves_recurrent_arch():
    import jax
    from repro.configs.base import get_config, reduced
    from repro.launch.mesh import mesh_for_devices
    from repro.models.model import Model
    from repro.serve import Engine, Request

    cfg = reduced(get_config("xlstm-350m"))
    mesh = mesh_for_devices(1)
    with pytest.raises(ValueError, match="legacy"):
        Engine(cfg, mesh, slots=2, max_len=16, cache_mode="paged")

    eng = Engine(cfg, mesh, slots=2, max_len=16)   # auto -> legacy
    assert eng.cache_mode == "legacy" and eng.pool is None
    eng.load(Model(cfg, mesh).init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n),
                    max_new_tokens=3) for i, n in enumerate((3, 5))]
    res = eng.run_to_completion(reqs)
    assert not res.truncated and sorted(res) == [0, 1]
    assert all(len(v) == 3 for v in res.values())


# ------------------------------------------- spans, counters and scopes


def _host_spans(trace_dir):
    """``[(name, stats)]`` of the ``serve.*`` host events a profiler
    wrote under ``trace_dir``, in start order."""
    import glob

    import jax

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    evs = [(e.start_ns, e.name, dict(e.stats))
           for plane in jax.profiler.ProfileData.from_file(path).planes
           for line in plane.lines for e in line.events
           if e.name.startswith("serve.")]
    return [(name, stats) for _, name, stats in sorted(evs)]


def _recording_plans(monkeypatch, eng):
    plans = []
    inner = eng.sched.plan

    def plan():
        p = inner()
        if p is not None:
            plans.append(p)
        return p

    monkeypatch.setattr(eng.sched, "plan", plan)
    return plans


def test_run_trace_writes_every_span_with_tick_ids(stack, paged_engine,
                                                   tmp_path):
    import jax

    paged_engine.run_to_completion(_reqs(stack, lens=(5, 9)))  # compiled
    reqs = _reqs(stack, lens=(5, 9, 3), new=3)
    jax.profiler.start_trace(str(tmp_path))
    try:                                  # the loop waits for arrivals
        res = paged_engine.run_trace(reqs, [0.05, 0.05, 0.1])
    finally:
        jax.profiler.stop_trace()
    assert not res.truncated and sorted(res) == [0, 1, 2]
    spans = _host_spans(tmp_path)
    names = {n for n, _ in spans}
    assert names == {"serve.admit", "serve.plan", "serve.prepare",
                     "serve.dispatch", "serve.readback", "serve.record",
                     "serve.wait"}
    ticks = {n: [st["tick"] for m, st in spans if m == n] for n in names
             if n != "serve.wait"}
    dispatched = ticks["serve.dispatch"]
    assert dispatched == list(range(dispatched[0], dispatched[0]
                                    + len(dispatched)))
    assert ticks["serve.prepare"] == dispatched
    assert sorted(ticks["serve.readback"]) == dispatched   # every tick read
    assert ticks["serve.record"] == ticks["serve.readback"]
    assert set(ticks["serve.plan"]) <= set(dispatched + [dispatched[-1] + 1])
    assert {st["kind"] for m, st in spans if m == "serve.dispatch"} == \
        {"chunk", "decode"}


def test_stats_add_up_against_the_plans(stack, paged_engine, monkeypatch):
    plans = _recording_plans(monkeypatch, paged_engine)
    lens = (5, 9, 3, 7)
    res = paged_engine.run_trace(_reqs(stack, lens=lens, new=4),
                                 [0.02, 0.02, 0.03, 0.05])
    assert not res.truncated
    st = paged_engine.stats
    kinds = {k: sum(p.kind == k for p in plans) for k in ("chunk", "decode")}
    assert st.ticks == {k: n for k, n in kinds.items() if n}
    assert st.dispatched == len(plans)
    chunks = [p for p in plans if p.kind == "chunk"]
    assert st.chunk_tokens == sum(int(p.lengths.sum()) for p in chunks) \
        == sum(lens)
    assert st.chunk_rows == len(chunks) * paged_engine.slots \
        * paged_engine.sched.chunk
    assert st.decode_tokens == sum(int(p.lengths.sum()) for p in plans
                                   if p.kind == "decode") == 4 * 3
    assert st.btab_uploads >= 1 and st.resets >= 1
    assert st.readback_s > 0 and st.host_s > 0
    assert st.wait_s >= 0.015             # asleep until the first arrival
    assert st.loop_s > st.readback_s + st.wait_s


def test_admit_s_lies_between_arrival_and_first_token(stack, paged_engine):
    res = paged_engine.run_trace(_reqs(stack, lens=(5, 9, 3, 7), new=3),
                                 [0.0, 0.0, 0.01, 0.04])
    assert sorted(res.metrics) == [0, 1, 2, 3]
    for m in res.metrics.values():
        assert m["arrival_s"] <= m["admit_s"] <= m["arrival_s"] \
            + m["ttft_s"] <= m["done_s"]


def test_request_admitted_before_a_run_keeps_its_record(stack,
                                                        paged_engine):
    first, second = _reqs(stack, lens=(5, 9), new=3)
    assert paged_engine.admit(first)
    res = paged_engine.run_to_completion([second])
    assert sorted(res) == [0, 1]
    assert {rid: m["tokens"] for rid, m in res.metrics.items()} == \
        {0: 3, 1: 3}
    first_m = res.metrics[0]              # on this run's clock
    assert first_m["arrival_s"] == first_m["admit_s"] == 0.0
    assert 0.0 < first_m["ttft_s"] <= first_m["done_s"]


@pytest.mark.parametrize("width", [1, 4])
def test_paged_tick_program_names_its_gather_and_scatter(stack,
                                                         paged_engine,
                                                         width):
    """The tick program's ops carry the paged cache's scopes in their
    ``op_name`` metadata, in the decode (width 1) and chunk programs."""
    import re

    import jax.numpy as jnp
    from repro.serve.engine import _upload

    eng = paged_engine
    B = eng.slots
    text = eng._dispatch_fn.lower(
        eng.params, jnp.zeros((B, width), jnp.int32),
        jnp.zeros((B,), bool), jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32), _upload(eng.temps), eng.key,
        eng.next_buf, eng.cache, eng.btab).compile().as_text()
    scopes = set(re.findall(r'op_name="[^"]*/(paged_gather|paged_scatter)/',
                            text))
    assert scopes == {"paged_gather", "paged_scatter"}


# ------------------------------------------------- multi-rank drain (2dev)

DRAIN = """
import numpy as np, jax
from repro.configs.base import get_config, reduced
from repro.launch.mesh import mesh_for_devices
from repro.models.model import Model
from repro.serve import Engine, Request, agree_admission_count

cfg = reduced(get_config("gemma3-4b"))
mesh = mesh_for_devices(2)
eng = Engine(cfg, mesh, slots=2, max_len=32, block_size=8)
assert eng.comm.size == 2
assert agree_admission_count(eng.comm, 3) == 3    # SPMD identity
eng.load(Model(cfg, mesh).init(jax.random.PRNGKey(0)))

rng = np.random.default_rng(0)
reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n),
                max_new_tokens=3) for i, n in enumerate((5, 9, 3))]
res = eng.run_to_completion(reqs)                 # admission agreement +
assert not res.truncated and sorted(res) == [0, 1, 2]
# drain barrier: every rank idle, pool fully returned, no active slots
assert not eng.sched.active() and not eng.requests
assert eng.pool.used_blocks == 0 and eng.pool.committed == 0
eng.comm.sync()
print("OK", sorted(len(v) for v in res.values()))
"""


def test_multirank_drain_barrier_leaves_ranks_idle():
    out = run_py(DRAIN, ndev=2)
    assert "OK [3, 3, 3]" in out
