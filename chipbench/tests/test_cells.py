"""Whole runs of tiny cells on the CPU: the harness past its look for
an accelerator, with the timed path sound and broken underneath."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.tests import tiny
from repro.serve.engine import Engine


def test_serve_run_is_correct_and_reports_its_metrics():
    out, line = tiny.run_cell(tiny.serve_cell(), tiny.args(seconds=2.0))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0
    assert out.end_to_end["tokens_per_s"] > 0
    assert np.isfinite(out.end_to_end["ttft_p90_ms"])
    assert list(line)[-1] == "checks"


def test_serve_token_altered_where_produced(monkeypatch):
    sound = Engine._dispatch_body

    def altered(self, *a):
        nxt, next_buf, cache = sound(self, *a)
        bad = (nxt + 1) % self.cfg.vocab_size
        return bad, jnp.where(a[4] > 0, bad, next_buf), cache

    monkeypatch.setattr(Engine, "_dispatch_body", altered)
    _, line = tiny.run_cell(tiny.serve_cell(), tiny.args(seconds=2.0))
    assert line["correct"] is False
    assert line["checks"]["mean_logit_gap"]["value"] > \
        line["checks"]["mean_logit_gap"]["limit"]


def test_serve_traced_run_reads_no_device_on_the_cpu():
    out, line = tiny.run_cell(tiny.serve_cell(), tiny.args(seconds=1.5,
                                                            trace=1))
    assert line["correct"] is True
    assert out.run.ticks and out.window_s > 0
    # no device planes on the CPU: the trace readers find nothing
    assert line["metrics"] == {}


def test_comm_runs_sound_and_catch_faults():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-m", "chipbench.tests.tiny",
                          "comm-faults"], cwd=tiny.ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    assert got == {"none": True, "altered": False, "left_out": False,
                   "control": False}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_is_not_correct(seed):
    """The control (the float32 reference run at float8 e4m3), read by
    ``control.py`` at a size a test run holds: the cell's own check
    fails it and passes the program, whose mean gap is several times
    smaller, with fewer tokens off the reference's best."""
    from chipbench.lib.harness import BENCH, load_module
    import jax
    control = load_module(BENCH / "control.py")
    cell = tiny.serve_cell()
    cell.config["model"].update(d_model=256, num_layers=4, vocab_size=2048,
                                head_dim=64, d_ff=1024)
    got = control.serve_readings(cell, seed, 3.0, jax.devices()[:1])
    program, ctl = got["program"], got["control"]
    assert program["correct"] is True, program
    assert ctl["correct"] is False, ctl
    assert ctl["mean"] >= 3 * program["mean"]
    assert ctl["flipped"] > program["flipped"]
