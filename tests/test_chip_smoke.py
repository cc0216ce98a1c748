"""The phases of ``chip_smoke.py`` rehearsed on the CPU at reduced size:
serving h2o-danube-1.8b (reduced widths, window widened so the cache
stays paged) checked against the float32 reference, the Communicator
phase on four virtual devices, and the refusals — no TPU, or the script
without the repository — that must end in a non-zero exit and no
result line."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from tests._subproc import ROOT, run_py


@pytest.fixture(scope="module")
def served():
    from repro.configs.base import get_config, reduced

    import chip_smoke as cs
    cfg = reduced(get_config(cs.ARCH), sliding_window=64)
    engine, params = cs.build_engine(cfg, slots=3, max_len=64, seed=0)
    reqs = cs.make_requests(cfg.vocab_size, 5, (4, 24), 6, seed=0)
    res, _ = cs.serve(engine, reqs)
    return engine, params, reqs, res


def test_serving_phase_matches_float32_reference(served):
    import chip_smoke as cs

    engine, params, reqs, res = served
    assert engine.cache_mode == "paged" and engine.paged_entries
    assert [len(res[r.rid]) for r in reqs] == [6] * 5
    gap, first = cs.reference_check(engine.model, params, reqs, res)
    assert 0.0 <= gap <= cs.TOKEN_MARGIN
    assert cs.prefill_check(engine.model, params, reqs, first) \
        <= cs.PREFILL_ATOL


def test_reference_check_rejects_a_wrong_token(served):
    import chip_smoke as cs

    engine, params, reqs, res = served
    r = reqs[1]
    bad = {q.rid: list(res[q.rid]) for q in reqs}
    # the token the reference ranks lowest at the first served position
    toks = np.concatenate([r.prompt, bad[r.rid]])[None]
    lg = np.asarray(engine.model.logits(params, toks))[0, len(r.prompt) - 1]
    bad[r.rid][0] = int(np.argmin(lg))
    with pytest.raises(AssertionError, match=f"request {r.rid}"):
        cs.reference_check(engine.model, params, reqs, bad)


def test_serving_phase_end_to_end_reduced():
    from repro.configs.base import get_config, reduced

    import chip_smoke as cs
    out = cs.serving_phase(reduced(get_config(cs.ARCH), sliding_window=64),
                           slots=2, max_len=48, n_requests=3,
                           prompt_lens=(3, 20), new_tokens=4, seed=1)
    assert out["tokens"] == 12 and out["weights_bytes"] > 0
    assert out["cache_bytes"] > 0 and out["worst_gap"] <= cs.TOKEN_MARGIN


COMM = """
import sys
sys.path.insert(0, {root!r})
import jax, numpy as np
import chip_smoke as cs
print("checks", cs.comm_phase(jax.devices()))
mesh = cs.comm_meshes(jax.devices())["pod2x2"]
x = np.arange(8, dtype=np.float32).reshape(4, 2)
fn = cs.comm_program(mesh, "tree")
out = fn(jax.device_put(x, jax.NamedSharding(mesh, jax.P(mesh.axis_names))))
want = cs.oracle(x)["allreduce"].copy()
want[2, 1] += 1
try:
    cs.check_output("allreduce", out[5], want, mesh)
except AssertionError as e:
    print("caught", e)
"""


def test_four_chip_phase_on_virtual_devices():
    out = run_py(COMM.format(root=ROOT), ndev=4)
    assert "checks 210" in out              # 2 meshes x 3 sizes x 35
    assert "caught allreduce: rank 2 element 1" in out


def _run_script(cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run_script(ROOT, env)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_fails_without_the_repository(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _run_script(tmp_path, env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
