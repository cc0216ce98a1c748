"""The trace reduction, on hand-made planes and on a recorded excerpt
of a chip trace kept beside this file."""
import os

import pytest

from chipbench.tests import tiny  # noqa: F401
from chipbench.lib import trace as tl

HERE = os.path.dirname(os.path.abspath(__file__))


def _ev(name, start, dur, **stats):
    return {"name": name, "start_ns": start, "duration_ns": dur,
            "stats": stats}


def _planes():
    # device 0 busy [10,30) and [25,40) and [60,70); device 1 [0,100)
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                _ev("fusion.1", 10, 20, hlo_module="jit_tick"),
                _ev("gather.2", 25, 15, hlo_module="jit_tick"),
                _ev("fusion.1", 60, 10, hlo_module="jit_tick")]},
            {"name": "XLA Modules", "events": [
                _ev("jit_tick(7)", 10, 30), _ev("jit_tick(7)", 60, 10)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [_ev("fusion.9", 0, 100)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                _ev("cb:traced", 0, 100), _ev("cb:finish", 40, 25),
                _ev("other", 0, 5)]}]},
    ]


def test_planes_split_into_ops_modules_spans():
    tr = tl.from_planes(_planes())
    assert tr.devices == ["/device:TPU:0", "/device:TPU:1"]
    assert len(tr.modules["/device:TPU:0"]) == 2
    assert [s.name for s in tr.spans] == ["cb:traced", "cb:finish"]


def test_busy_is_the_union_averaged_over_devices():
    tr = tl.from_planes(_planes())
    # device 0: [10,40) + [60,70) = 40; device 1: 100
    assert tl.busy_ns(tr, 0, 100) == pytest.approx(70)
    assert tl.busy_ns(tr, 20, 65) == pytest.approx((20 + 5 + 45) / 2)


def test_gaps_and_their_labels():
    tr = tl.from_planes(_planes())
    gaps = tl.idle_gaps(tr, 0, 100)
    # device 0 idles [0,10), [40,60) and [70,100)
    assert [round(s * 1e9) for _, s in gaps] == [30, 20, 10]
    assert [label for label, _ in gaps] == ["cb:traced", "cb:finish",
                                            "cb:traced"]


def test_top_ops_sum_by_name_per_device():
    tr = tl.from_planes(_planes())
    top = dict(tl.top_ops(tr, 0, 100))
    assert top["fusion.1"] == pytest.approx(30e-9 / 2)
    assert top["fusion.9"] == pytest.approx(100e-9 / 2)


def test_op_label_keeps_name_type_and_opcode():
    text = ("%fusion.3 = (f32[32,8]{1,0:T(8,128)S(1)}, bf16[2]{0}) "
            "fusion(bf16[32,8]{1,0} %p.1), kind=kOutput")
    assert tl.op_label(text) == "%fusion.3 f32[32,8] fusion"


def test_intersect():
    assert tl.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10),
                                                           (20, 25)]


RECORDED = os.path.join(HERE, "data", "danube_decode_excerpt.json.gz")
# An excerpt of a chip trace (h2o-danube-1.8b at 32 slots, TPU v5 lite):
# a chunk tick, then a decode tick, then a chunk tick, with the
# benchmark's host spans; ``tl.planes_of`` of the run's ``.xplane.pb``,
# cut to those events and written with ``gzip`` + ``json.dump``.  The
# expected numbers were counted by a separate sweep over its events.
EXCERPT_OPS = (3035431960.0, 4197731795.0)   # first op start, last op end
EXCERPT_BUSY = 1162276976.0


def test_recorded_excerpt_busy_and_programs():
    tr = tl.load_json(RECORDED)
    assert tr.devices == ["/device:TPU:0"]
    lo, hi = EXCERPT_OPS
    assert tl.busy_ns(tr, lo, hi) == pytest.approx(EXCERPT_BUSY, abs=1)
    ticks = [e.dur for e in tr.modules["/device:TPU:0"]
             if "_dispatch_body" in e.name]
    assert ticks == [507282128.0, 507252584.0, 154301123.0, 507137963.0]
    assert [s.name for s in tr.spans].count("cb:dispatch.decode") == 1


def test_recorded_excerpt_pairs_ticks_with_programs():
    """The two traced dispatches (decode, then chunk) pair with the last
    two executions of the tick program, whatever ran before them."""
    from chipbench.lib.harness import BENCH, load_module
    serve = load_module(BENCH / "kinds" / "serve.py")
    chunk_ms = load_module(BENCH / "metrics" / "chunk_tick_ms.py")
    tr = tl.load_json(RECORDED)
    span = tl.spans_named(tr, "cb:traced")[0]
    model = {"num_layers": 24, "d_model": 2560, "num_heads": 32,
             "num_kv_heads": 8, "head_dim": 80, "d_ff": 6912,
             "vocab_size": 32000, "sliding_window": 4096}
    ticks = [serve.Tick("decode", 1.0, 0.004, [(1000, 1)] * 32),
             serve.Tick("chunk", 2.0, 0.004, [(0, 256)] * 2)]
    run = serve.ServeRun(model, {}, ticks, [], trace=tr, traced_from_s=0.0,
                         lo_ns=span.start, hi_ns=span.end)
    pairs = run.tick_programs()
    assert [(t.kind, e.dur) for t, e in pairs] == [
        ("decode", 154301123.0), ("chunk", 507137963.0)]
    assert chunk_ms.read(run) == pytest.approx(507.137963)


def test_pairs_from_the_end_and_the_clock_shift():
    # three blocking spans; the device clock runs 2 units behind, and an
    # execution from before the first span is in the trace too
    spans = [tl.Event("cb:a", 10, 6, {}), tl.Event("cb:b", 20, 6, {}),
             tl.Event("cb:c", 30, 6, {})]
    runs = [tl.Event("p0", 0, 2, {}), tl.Event("p1", 9, 3, {}),
            tl.Event("p2", 19, 3, {}), tl.Event("p3", 29, 3, {})]
    pairs = tl.pair_from_end(spans, runs)
    assert [(s.name, r.name) for s, r in pairs] == [
        ("cb:a", "p1"), ("cb:b", "p2"), ("cb:c", "p3")]
    # each execution must fit its span: shift in [1, 4]; midway 2.5
    assert tl.device_shift(pairs) == pytest.approx(2.5)
