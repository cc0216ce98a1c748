"""A second serving architecture that exists only as this test's files:
``second_arch/gsuper_decoder.py``, a reference module with its own
weights, program layout (the program's ``gsuper`` group, not
``dec_dec``) and counts, and ``second_arch/tiny-gsuper.json``, a
configuration that names it.  No benchmark code names either: the
serving kind finds a configuration's reference through
``reference_of``, which the tests point at this directory, as
``chipbench/references/<name>.py`` would be found."""
import dataclasses

import jax
import pytest

from chipbench.tests import tiny
from chipbench.lib import trace as tl
from chipbench.lib.harness import BENCH, Cell, load_module, read_json
from chipbench.peaks import PEAKS

HERE = BENCH / "tests" / "second_arch"
serve = load_module(BENCH / "kinds" / "serve.py")
dense = load_module(BENCH / "references" / "dense_decoder.py")
gsuper = load_module(HERE / "gsuper_decoder.py")
step_mfu = load_module(BENCH / "metrics" / "step_mfu.py")
roofline = load_module(BENCH / "metrics" / "decode_tick_roofline.py")


def gsuper_cell(**model) -> Cell:
    conf = read_json(HERE / "tiny-gsuper.json")
    conf["model"].update(model)
    return Cell("tiny-gsuper", 1, conf["name"], conf, "chat-azure-conv",
                tiny.serve_mix(), [], [])


@pytest.fixture(scope="module")
def reference_here():
    """``reference_of`` finding the test's reference module by name."""
    find = serve.reference_of

    def reference_of(conf):
        if conf["reference"] == "gsuper_decoder":
            return gsuper
        return find(conf)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serve, "reference_of", reference_of)
        yield


@pytest.fixture(scope="module")
def served(reference_here):
    return tiny.run_cell(gsuper_cell(), tiny.args(seconds=2.0))


def test_program_layout_is_the_references_own():
    model = read_json(HERE / "tiny-gsuper.json")["model"]
    w = jax.eval_shape(lambda k: gsuper.init_weights(model, k),
                       jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    params = gsuper.program_params(w, model)
    assert sorted(params) == ["dec_gsuper", "emb", "final_norm"]
    assert len(params["dec_gsuper"]) == 2           # windowed, global
    assert params["dec_gsuper"][1]["wq"].shape == (2, 64, 64)


def test_second_arch_runs_correct_through_the_serve_kind(served):
    out, line = served
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and out.end_to_end["tokens_per_s"] > 0
    assert out.run.counts is gsuper
    assert {t.kind for t in out.run.ticks} == {"chunk", "decode"}


# One decode row at position 40 of the tiny configuration (4 layers: 2
# windowed at 32, 2 global; D 64, 4 heads and 2 KV heads of 16, d_ff
# 128, vocab 256, LM head tied):
# FLOPs: matmuls 2*4*(64*64 + 2*64*32 + 64*64 + 3*64*128) = 294912,
#   LM head 2*64*256 = 32768, attention 4*4*16 per key per layer over
#   2*32 + 2*41 keys = 37376; total 365056.
# Bytes: weights (4*(36864 + 2*64) + 64 + 64*256) * 2 = 328832, the
#   embedding row 128, K/V 2*2*16*2 = 128 B a token a layer for
#   2*(32 + 1) + 2*(41 + 1) = 150 rows read or written = 19200;
#   total 348160.
FLOPS_AT_40, BYTES_AT_40 = 365056, 348160


def test_second_arch_counts_by_hand():
    model = gsuper_cell().config["model"]
    assert gsuper.tick_flops(model, [(40, 1)]) == FLOPS_AT_40
    assert gsuper.decode_bytes(model, [(40, 1)]) == BYTES_AT_40
    # the dense decoder's counts would window every layer
    assert dense.tick_flops(model, [(40, 1)]) == 294912 + 32768 + 256 * 128


def stand_in_trace(run, tick_ns: float):
    """``run`` with a trace in which each of its ticks ran its program
    for ``tick_ns``, one after the other with gaps of the same length."""
    step = 2 * tick_ns

    def events(name):
        return [{"name": name, "start_ns": i * step, "duration_ns": tick_ns}
                for i in range(len(run.ticks))]

    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": events("fusion.1")},
        {"name": "XLA Modules", "events": events("jit__dispatch_body(1)")}]}]
    return dataclasses.replace(run, trace=tl.from_planes(planes),
                               traced_from_s=0.0, lo_ns=0.0,
                               hi_ns=len(run.ticks) * step)


def test_readers_take_the_second_arch_counts_exactly(served):
    out, _ = served
    peaks = PEAKS["TPU v5 lite"]
    one = dataclasses.replace(out.run, ticks=[
        serve.Tick("decode", 0.0, 0.001, [(40, 1)])])
    run = stand_in_trace(one, 1000.0)             # 1 us, in a 2-us window
    assert step_mfu.read(run) == pytest.approx(
        100 * FLOPS_AT_40 / 2e-6 / peaks["bf16_flops_per_s"], rel=1e-12)
    assert roofline.read(run) == pytest.approx(
        100 * BYTES_AT_40 / peaks["hbm_bytes_per_s"] / 1e-6, rel=1e-12)


def test_readers_count_the_served_ticks_with_the_second_arch(served):
    out, _ = served
    run = stand_in_trace(out.run, 1e6)
    model, ticks = run.model, run.ticks
    decode = [t for t in ticks if t.kind == "decode"]
    window = len(ticks) * 2e-3
    peaks = PEAKS["TPU v5 lite"]

    def mfu(counts):
        work = sum(counts.tick_flops(model, t.rows) for t in ticks)
        return 100 * work / window / peaks["bf16_flops_per_s"]

    def share(counts):
        least = sum(max(counts.tick_flops(model, t.rows)
                        / peaks["bf16_flops_per_s"],
                        counts.decode_bytes(model, t.rows)
                        / peaks["hbm_bytes_per_s"]) for t in decode)
        return 100 * least / (len(decode) * 1e-3)

    assert step_mfu.read(run) == pytest.approx(mfu(gsuper), rel=1e-12)
    assert roofline.read(run) == pytest.approx(share(gsuper), rel=1e-12)
    assert mfu(gsuper) != pytest.approx(mfu(dense), rel=1e-6)


def test_second_arch_control_is_not_correct(reference_here):
    """``control.py``'s serving readings of the second architecture, at
    the size the dense decoder's control test takes: the configuration's
    limit passes the program and fails its reference's float8 control."""
    control = load_module(BENCH / "control.py")
    cell = gsuper_cell(d_model=256, vocab_size=2048, head_dim=64,
                       d_ff=1024)
    got = control.serve_readings(cell, 1, 3.0, jax.devices()[:1])
    program, ctl = got["program"], got["control"]
    assert program["correct"] is True, program
    assert ctl["correct"] is False, ctl
    assert ctl["mean"] >= 3 * program["mean"]
