"""The reduction of the serving engine's own spans, counters and scopes
(``lib/engine_trace.py``): on hand-made planes, on a recorded excerpt of
a chip trace kept beside this file, and in tiny whole windows on the
CPU."""
import os

import numpy as np
import pytest

from chipbench.tests import tiny
from chipbench.lib import engine_trace as et
from chipbench.lib import trace as tl

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6


def _ev(name, start, dur, **stats):
    return {"name": name, "start_ns": start, "duration_ns": dur,
            "stats": stats}


def _pipelined():
    """Twenty decode ticks (ids 10-29) of a pipelined loop, in ms: the
    device runs tick n over [20n, 20n + 18) of its clock; the host's
    clock reads 100 ms more, and a read-back ends 0.1 ms after its
    program.  Tick n is dispatched during tick n-1's program (host
    100 + 20n - 15) and read back after tick n+1 is dispatched.  The
    trace misses tick 17's program; after reading back tick 23 the host
    sleeps for arrivals and then dispatches tick 24 onto an idle device;
    tick 29 is never read back; and tick 9's program, dispatched before
    the trace began, is in it.
    One op a tick: tick 12's program is all under ``paged_gather``."""
    runs, ops, host = [], [], []
    for n in range(9, 30):
        s = 20.0 * n + (40 if n >= 24 else 0)
        if n != 17:
            runs.append(_ev("jit__dispatch_body(1)", s * MS, 18 * MS))
            ops.append(_ev(f"%fusion.{n} = bf16[8,2048,8,80]{{}} fusion()",
                           s * MS, 18 * MS))
        if n == 9:
            continue
        d = s + 100 - 15 if n != 24 else s + 100 - 1
        host.append(_ev("serve.dispatch", d * MS, 1 * MS, tick=n,
                        kind="decode"))
        if n != 29:
            host.append(_ev("serve.readback", (d + 3) * MS,
                            (s + 118.1 - d - 3) * MS, tick=n))
    host.append(_ev("serve.wait", (20 * 23 + 118.1 + 1) * MS, 35 * MS))
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": runs},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": sorted(
                host, key=lambda e: e["start_ns"])}]}]
    return tl.from_planes(planes), et.from_planes(planes)


def test_shift_from_read_back_ends_and_ticks_matched_by_id():
    trace, prog = _pipelined()
    ticks, runs = et.ticks_of(prog), et.runs_of(trace)
    assert [t.tick for t in ticks] == list(range(10, 30))
    pairs = et.bracket_pairs(ticks, runs)
    # ticks 18-28 pair with their own programs (d = 100.1 ms); before
    # the missed one every pair is a tick off (d = 120.1 ms or more)
    assert et.shift_by_ends(pairs) == pytest.approx(100.1 * MS)
    # the midpoint of both bounds leans on the dispatch side, which lies
    # 15 ms or more before a program starts: here 105 (the pairs a tick
    # off) against 100.1
    assert tl.device_shift(pairs) == pytest.approx((105 + 100.1) / 2 * MS)
    et.match(ticks, runs, et.shift_by_ends(pairs))
    got = {t.tick: t.run.start / MS if t.run else None for t in ticks}
    assert got[17] is None
    assert got[10] == 200.0 and got[16] == 320.0 and got[18] == 360.0
    assert got[24] == 520.0 and got[29] == 620.0       # never read back


def test_tick_gaps_leave_out_waits_and_untraced_ticks():
    trace, prog = _pipelined()
    ticks, runs = et.ticks_of(prog), et.runs_of(trace)
    shift = et.shift_by_ends(et.bracket_pairs(ticks, runs))
    et.match(ticks, runs, shift)
    gaps, missed = et.tick_gaps(ticks, prog.named("serve.wait"), shift)
    # 19 traced ticks give 18 neighbours: (16, 18) has tick 17 between
    # them, (23, 24) holds the wait; the other 16 are 2 ms apart
    assert missed == 1
    assert len(gaps) == 16 and set(gaps) == {2 * MS}


def test_long_gaps_name_the_missed_execution_and_the_wait():
    trace, prog = _pipelined()
    ticks, runs = et.ticks_of(prog), et.runs_of(trace)
    shift = et.shift_by_ends(et.bracket_pairs(ticks, runs))
    et.match(ticks, runs, shift)
    gaps = et.long_gaps(trace, prog, ticks, shift, 180 * MS, 640 * MS)
    got = [(round(g["at_s"] * 1e3), round(g["dur_s"] * 1e3), g["case"],
            g["tick_before"], g["tick_after"], g["ticks_missing"])
           for g in gaps]
    assert got == [(158, 22, "missed execution", 16, 18, [17]),
                   (298, 42, "arrival wait", 23, 24, [])]


def test_op_shares_by_scope_and_pool_shape():
    trace, prog = _pipelined()
    ticks, runs = et.ticks_of(prog), et.runs_of(trace)
    et.match(ticks, runs, et.shift_by_ends(et.bracket_pairs(ticks, runs)))
    scopes = {"decode": {"fusion.12": "jit(_dispatch_body)/while/body/"
                         "paged_gather/jit(_take)/gather",
                         "fusion.13": "jit(_dispatch_body)/while/body/"
                         "dynamic_slice"}}
    got = et.op_shares(trace, ticks, scopes, pool_dims=(2048, 8, 80))
    assert got["program"] == 19 * 18 * MS and got["ops"] == 19 * 18 * MS
    assert got["mapped"] == 2 * 18 * MS
    assert got["scoped"] == 18 * MS and got["pool"] == 18 * MS


def test_scope_map_of_compiled_text():
    text = """
  %fusion.185 = bf16[24576,32,128]{2,1,0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(_dispatch_body)/while/body/paged_gather/jit(_take)/gather" source_file="x.py" source_line=1}
  ROOT %copy.71 = bf16[10,1536,16,32,128]{4,3,2,1,0} copy(%q), metadata={op_name="jit(_dispatch_body)/while/body/dynamic_update_slice"}
  %param.1 = s32[6]{0} parameter(0)
"""
    assert et.scope_map(text) == {
        "fusion.185": "jit(_dispatch_body)/while/body/paged_gather/"
        "jit(_take)/gather",
        "copy.71": "jit(_dispatch_body)/while/body/dynamic_update_slice"}


RECORDED = os.path.join(HERE, "data", "deepseek_engine_excerpt.json.gz")
# An excerpt of a chip trace (deepseek-longdoc, TPU v5 lite, seed
# 7320000001): ticks 492 (decode), 493 and 494 (chunk).  Cut from the
# trace ``engine_readings.py`` read: the device's programs and ops from
# 2 ms before tick 492's program to the end of tick 494's, the
# program's spans of those ticks, the benchmark's spans around them,
# and under "scopes" {kind: {instruction: op_name}} from the compiled
# text of the two tick programs, for the instructions in the cut.  The
# expected numbers were counted by a separate sweep over its events.


def test_recorded_excerpt_shift_gaps_and_paged_share():
    trace, prog, scopes = et.load_excerpt(RECORDED)
    # the program's spans stay out of the benchmark's own
    assert {s.name for s in trace.spans} == {
        "cb:traced", "cb:plan", "cb:admit", "cb:dispatch.chunk",
        "cb:finish"}
    ticks, runs = et.ticks_of(prog), et.runs_of(trace)
    assert [(t.tick, t.kind) for t in ticks] == [
        (492, "decode"), (493, "chunk"), (494, "chunk")]
    pairs = et.bracket_pairs(ticks, runs)
    shift = et.shift_by_ends(pairs)
    assert shift == pytest.approx(1361168.4)
    # each tick was dispatched 64-269 ms before its program started: the
    # midpoint of both bounds lands 31.6 ms the wrong side of zero
    assert tl.device_shift(pairs) == pytest.approx(-31557060.35)
    et.match(ticks, runs, shift)
    assert [t.run.dur for t in ticks] == [67240233.0, 270730417.0,
                                          270628352.0]
    gaps, missed = et.tick_gaps(ticks, prog.named("serve.wait"), shift)
    assert missed == 0 and gaps == [13132.0, 9983.0]   # 11.5575 us
    got = et.op_shares(trace, ticks, scopes)
    # 13.49% over the three ticks; the decode tick's alone is 23.2%
    assert got["program"] == 608599002.0
    assert got["scoped"] == 82116797.0


def test_counters_read_from_engine_stats():
    from repro.serve.engine import EngineStats
    st = EngineStats(ticks={"chunk": 4, "decode": 6}, chunk_tokens=1000,
                     chunk_rows=4 * 4096, loop_s=1.0, readback_s=0.7,
                     wait_s=0.2)
    assert et.host_self_ms(st) == pytest.approx(10.0)
    assert et.chunk_fill(st) == pytest.approx(100 * 1000 / 16384)
    assert et.host_self_ms(None) is None and et.chunk_fill(EngineStats()) \
        is None
    records = {1: {"arrival_s": 0.0, "admit_s": 0.05},
               2: {"arrival_s": 1.0, "admit_s": 1.2}}
    # request 3 is still queued at the close (10 s): its wait so far
    waits = [0.05, 0.2, 10.0 - 9.5]
    assert et.queue_wait_p90_ms(records, [(1, 0.0), (2, 1.0), (3, 9.5)],
                                10.0) == pytest.approx(
        np.percentile(waits, 90) * 1e3)
    assert et.queue_wait_p90_ms({1: {"arrival_s": 0.0}}, [(1, 0.0)],
                                10.0) is None


def test_tiny_window_reads_the_engine_counters():
    """A whole traced window of the tiny serving cell through
    ``engine_readings.py``: the three counter readings are read; there
    are no device planes on the CPU, so the trace's readings are not,
    though every dispatched tick's span is in the trace."""
    import jax
    from chipbench.lib.harness import BENCH, load_module
    from chipbench.peaks import PEAKS
    er = load_module(BENCH / "engine_readings.py")
    out = er.readings(tiny.serve_cell(), tiny.args(seconds=1.5, trace=1),
                      jax.devices()[:1], PEAKS["TPU v5 lite"])
    eng = out["engine"]
    for name in ("host_self_ms", "queue_wait_p90_ms", "chunk_fill"):
        assert eng[name] is not None and eng[name] > 0, name
    assert 0 < eng["chunk_fill"] <= 100
    st = eng["stats"]
    assert st["ticks"]["chunk"] > 0 and st["ticks"]["decode"] > 0
    assert eng["tick_gap_us"] is None and eng["paged_kv_share"] is None
    traced = sum(b - a + 1 for a, b in eng["untraced_ticks"])
    assert 0 < traced <= sum(st["ticks"].values())
    assert out["end_to_end"]["tokens_per_s"] > 0


@pytest.mark.parametrize("config,want", [
    ("h2o-danube-1.8b", (2048, 16, 640)),          # head_dim 80: flat
    ("deepseek-7b", (1536, 16, 32, 128))])
def test_pool_dims_from_the_engine_cache_specs(config, want):
    """One layer's shape of the paged pool of the cell's own engine
    (built, not loaded: no weights and no cache are made)."""
    from chipbench.lib.harness import BENCH, load_module, read_json
    from repro.launch.mesh import mesh_for_devices
    from repro.serve import Engine
    er = load_module(BENCH / "engine_readings.py")
    serve = load_module(BENCH / "kinds" / "serve.py")
    conf = read_json(BENCH / "configs" / f"{config}.json")
    s = conf["serving"]
    eng = Engine(serve.arch_config(conf), mesh_for_devices(1),
                 slots=s["slots"], max_len=s["max_len"],
                 block_size=s["block_size"], cache_mode="paged")
    assert er.pool_dims(eng) == want
