#!/usr/bin/env python3
"""Bring-up smoke test: the system's main paths on a TPU.

    python chip_smoke.py               # serve h2o-danube-1.8b on one chip
    python chip_smoke.py --four-chip   # the Communicator across four chips

Default: ``h2o-danube-1.8b`` at its published config (all 24 layers,
random weights from ``--seed``) is served through the same calls as
``python -m repro.launch.serve``: ``mesh_for_devices`` ->
``Engine(cache_mode="auto")`` (paged KV cache) -> ``Model.init`` ->
``Engine.load`` -> ``Engine.run_trace``.  16 requests with prompts of
64-1024 tokens get 32 greedy tokens each.  Every served token is checked
against a cache-free float32 forward of the same weights, and the first
token's logits from ``Model.prefill`` against that forward.

``--four-chip``: send/recv, bcast, agg, scatter, allreduce and alltoall
on the native, tree, serial and hier transports, over a flat 4-rank mesh
and a 2x2 ("pod", "data") mesh, at 8 B, 8 KB and 8 MB per rank.  Each
result is compared bit for bit with a NumPy oracle and with lax's own
collective, and each output shard must sit on its own rank's device.

Without a TPU the script exits non-zero and prints no result.  Its last
line of output is one JSON object naming the device.  The phases are
functions, so the CPU tests rehearse them at reduced size.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.comms import Communicator  # noqa: E402
from repro.configs.base import ArchConfig, get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh, mesh_for_devices  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serve import Engine, Request  # noqa: E402

ARCH = "h2o-danube-1.8b"
SLOTS, MAX_LEN = 8, 2048          # max_len <= the 4096 window: paged cache
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 16, (64, 1024), 32
# A served token passes when its float32 reference logit lies within
# TOKEN_MARGIN of that position's reference maximum.  Random-init logits
# are ~N(0, 1) over the vocabulary (the top one near 4), so a wrong
# token misses by O(1); bf16 serving moves a logit by a few hundredths.
TOKEN_MARGIN = 0.25
# Model.prefill's bf16 first-token logits vs the float32 reference.
PREFILL_ATOL = 0.25

TRANSPORTS = ("native", "tree", "serial", "hier")
SIZES = (8, 8 << 10, 8 << 20)     # payload bytes per rank
ROOT = 1                          # non-zero root for bcast / agg / scatter
SEND, RECV = (0, 3), (1, 2)       # (src, dst) of the send and recv checks
OPS = ("send", "recv", "bcast", "agg", "scatter", "allreduce", "alltoall")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(count: int):
    """The devices, or exit non-zero when JAX finds fewer TPU chips."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no TPU found: {e}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU found: JAX sees {devices[0].platform}")
    if len(devices) < count:
        raise SystemExit(f"need {count} TPU chips, JAX sees {len(devices)}")
    return devices


def nbytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


class CompileClock:
    """Seconds spent in XLA backend compiles while it is installed."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


# ------------------------------------------------------------ serving phase


def make_requests(vocab: int, n: int, lens, new: int, seed: int):
    """``n`` seeded requests; the first two take the shortest and the
    longest prompt length so both ends of the range are served."""
    rng = np.random.default_rng(seed)
    lo, hi = lens
    sizes = rng.integers(lo, hi + 1, size=n)
    sizes[:2] = (lo, hi)
    return [Request(rid=i, prompt=rng.integers(0, vocab, size=int(s),
                                               dtype=np.int32),
                    max_new_tokens=new)
            for i, s in enumerate(sizes)]


def build_engine(cfg: ArchConfig, slots: int, max_len: int, seed: int):
    mesh = mesh_for_devices(len(jax.devices()))
    engine = Engine(cfg, mesh, slots=slots, max_len=max_len,
                    cache_mode="auto")
    params = Model(cfg, mesh).init(jax.random.PRNGKey(seed))
    engine.load(params)
    return engine, params


def warm_up(engine: Engine, prompt) -> None:
    """Serve one short request twice: compiles the chunk tick, the
    decode tick and the slot reset."""
    for rid in (-1, -2):
        engine.run_trace([Request(rid=rid, prompt=prompt[:8],
                                  max_new_tokens=2)], [0.0])


def serve(engine: Engine, reqs):
    """Serve ``reqs``, all arriving at once; raises unless every one
    completes with its full token budget.  Returns (results, wall
    seconds)."""
    t0 = time.perf_counter()
    res = engine.run_trace(reqs, [0.0] * len(reqs))
    wall = time.perf_counter() - t0
    if res.truncated or sorted(res) != sorted(r.rid for r in reqs):
        raise AssertionError(f"served {sorted(res)}, unfinished "
                             f"{sorted(res.unfinished)}")
    for r in reqs:
        if len(res[r.rid]) != r.max_new_tokens:
            raise AssertionError(f"request {r.rid}: {len(res[r.rid])} "
                                 f"tokens, expected {r.max_new_tokens}")
    return res, wall


def reference_check(model: Model, params, reqs, served, *, batch: int = 4,
                    margin: float = TOKEN_MARGIN):
    """Teacher-force prompt + served tokens through the cache-free
    float32 forward.  Raises unless every served token's reference logit
    is within ``margin`` of that position's reference maximum.  Returns
    (worst gap, {rid: float32 logits at the prompt's last position})."""
    new = max(len(served[r.rid]) for r in reqs)
    L = max(len(r.prompt) for r in reqs) + new
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def stats(p, toks, last):
        lg = model.logits(p, toks)                               # (b, L, V)
        nxt = jnp.take_along_axis(lg[:, :-1], toks[:, 1:, None], axis=-1)
        first = jnp.take_along_axis(lg, last[:, None, None], axis=1)
        return lg.max(-1), nxt[..., 0], first[:, 0]

    fn = jax.jit(stats)
    worst, first_logits = 0.0, {}
    for i in range(0, len(reqs), batch):
        group = reqs[i:i + batch]
        group = group + [group[-1]] * (batch - len(group))   # fixed shape
        toks = np.zeros((batch, L), np.int32)
        for b, r in enumerate(group):
            seq = np.concatenate([r.prompt, served[r.rid]])
            toks[b, :len(seq)] = seq
        last = np.array([len(r.prompt) - 1 for r in group], np.int32)
        with jax.default_matmul_precision("highest"):
            top, nxt, first = jax.device_get(fn(params32, toks, last))
        for b, r in enumerate(group):
            p, n = len(r.prompt), len(served[r.rid])
            gap = top[b, p - 1:p - 1 + n] - nxt[b, p - 1:p - 1 + n]
            worst = max(worst, float(gap.max()))
            if (gap > margin).any():
                j = int(np.argmax(gap))
                raise AssertionError(
                    f"request {r.rid}: served token {j} "
                    f"({served[r.rid][j]}) is {gap[j]:.4f} below the "
                    f"float32 reference maximum (margin {margin})")
            first_logits[r.rid] = first[b]
    return worst, first_logits


def prefill_check(model: Model, params, reqs, ref_first, *,
                  atol: float = PREFILL_ATOL):
    """``Model.prefill``'s last-position logits for the shortest and the
    longest prompt vs the float32 reference.  Returns the worst error."""
    fn = jax.jit(model.prefill)
    worst = 0.0
    for r in (min(reqs, key=lambda q: len(q.prompt)),
              max(reqs, key=lambda q: len(q.prompt))):
        lg, _ = fn(params, jnp.asarray(r.prompt[None]))
        err = float(np.abs(np.asarray(lg).reshape(-1)
                           - ref_first[r.rid]).max())
        worst = max(worst, err)
        if err > atol:
            raise AssertionError(f"request {r.rid}: prefill logits differ "
                                 f"from the float32 reference by {err:.4f}"
                                 f" (atol {atol})")
    return worst


def serving_phase(cfg: ArchConfig, *, slots: int, max_len: int,
                  n_requests: int, prompt_lens, new_tokens: int,
                  seed: int) -> dict:
    """Build, serve and check against the float32 reference; raises on
    any miss.  Returns the figures it printed."""
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        t0 = time.perf_counter()
        engine, params = build_engine(cfg, slots, max_len, seed)
        if engine.cache_mode != "paged":
            raise AssertionError(f"expected the paged cache, got "
                                 f"{engine.cache_mode}")
        w_bytes = nbytes(params)
        c_bytes = nbytes(engine.cache) + nbytes(engine.btab)
        log(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
            f"vocab {cfg.vocab_size}; weights {w_bytes} bytes, paged "
            f"cache {c_bytes} bytes ({engine.num_blocks} blocks of "
            f"{engine.block_size} tokens)")
        reqs = make_requests(cfg.vocab_size, n_requests, prompt_lens,
                             new_tokens, seed)
        warm_up(engine, reqs[0].prompt)
        setup, compile_s, n_compiled = (time.perf_counter() - t0,
                                        clock.seconds, clock.count)
        served, wall = serve(engine, reqs)
        tokens = sum(len(v) for v in served.values())
        log(f"set-up {setup:.1f} s including compile {compile_s:.1f} s "
            f"over {n_compiled} programs")
        log(f"served {len(served)} requests, {tokens} tokens, in "
            f"{wall:.3f} s after warm-up, with "
            f"{clock.count - n_compiled} compiles inside that run")
        log(f"peak_bytes_in_use after serving: {peak_bytes()}")
        model = engine.model
        del engine                  # frees the cache before the reference
        gc.collect()
        gap, ref_first = reference_check(model, params, reqs, served)
        err = prefill_check(model, params, reqs, ref_first)
        log(f"float32 reference: worst served-token gap {gap:.4f} "
            f"(margin {TOKEN_MARGIN}); prefill first-token max error "
            f"{err:.4f} (atol {PREFILL_ATOL}); peak_bytes_in_use "
            f"{peak_bytes()}")
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    return {"weights_bytes": w_bytes, "cache_bytes": c_bytes,
            "tokens": tokens, "serve_s": wall, "compile_s": compile_s,
            "worst_gap": gap, "prefill_err": err}


# ------------------------------------------------------ Communicator phase


def comm_meshes(devices):
    """A flat 4-rank mesh, and a 2x2 one whose hier allreduce runs both
    its in-pod and its cross-pod leg."""
    four = list(devices)[:4]
    return {"flat4": make_mesh((4,), ("data",), devices=four),
            "pod2x2": make_mesh((2, 2), ("pod", "data"), devices=four)}


def oracle(x: np.ndarray):
    """NumPy results per op; row r is rank r's output."""
    n, m = x.shape
    blk = -(-m // n)                  # per-destination block, zero-padded
    pad = np.zeros((n * blk,), x.dtype)
    pad[:m] = x[ROOT]
    xa = np.zeros((n, n * blk), x.dtype)
    xa[:, :m] = x
    send, recv = x.copy(), x.copy()
    send[SEND[1]] = x[SEND[0]]
    recv[RECV[1]] = x[RECV[0]]
    agg = np.zeros((n, n * m), x.dtype)
    agg[ROOT] = x.reshape(-1)
    a2a = xa.reshape(n, n, -1).transpose(1, 0, 2).reshape(n, -1)
    return {"send": send, "recv": recv,
            "bcast": np.broadcast_to(x[ROOT], x.shape),
            "agg": agg, "scatter": pad.reshape(n, blk),
            "allreduce": np.broadcast_to(x.sum(0), x.shape),
            "alltoall": a2a}


def _a2a_input(v, n):
    """Zero-pad a rank's payload to n equal blocks (the 8 B case: 2
    float32 values become 4, one per destination)."""
    m = v.shape[0]
    return jnp.pad(v, (0, -(-m // n) * n - m))


def comm_program(mesh, transport: str):
    """All seven ops of one transport in one jitted shard_map."""
    comm = Communicator(mesh, transport)
    axes, n = comm.axes, comm.size

    def body(x):
        v = x.reshape(-1)
        outs = (comm.send(v, SEND[1], src=SEND[0]),
                comm.recv(v, RECV[0], dst=RECV[1]),
                comm.bcast(v, root=ROOT), comm.agg(v, root=ROOT),
                comm.scatter(v, root=ROOT), comm.allreduce(v),
                comm.alltoall(_a2a_input(v, n)))
        return tuple(o.reshape(1, -1) for o in outs)

    return jax.jit(comm.wrap(body, in_specs=(P(axes),),
                             out_specs=(P(axes),) * len(OPS)))


def lax_program(mesh):
    """The same ops written directly with lax collectives."""
    axes = tuple(mesh.axis_names)
    n = mesh.size

    def body(x):
        v = x.reshape(-1)
        me = lax.axis_index(axes)

        def p2p(src, dst):
            got = lax.ppermute(v, axes, [(src, dst)])
            return jnp.where(me == dst, got, v)

        full = lax.all_gather(v, axes)                           # (n, m)
        m = v.shape[0]
        blk = -(-m // n)
        root = jnp.pad(full[ROOT], (0, n * blk - m))
        a2a = lax.all_to_all(_a2a_input(v, n).reshape(n, -1), axes, 0, 0)
        outs = (p2p(*SEND), p2p(*RECV), full[ROOT],
                jnp.where(me == ROOT, full.reshape(-1),
                          jnp.zeros((n * m,), v.dtype)),
                lax.dynamic_slice(root, (me * blk,), (blk,)),
                lax.psum(v, axes), a2a.reshape(-1))
        return tuple(o.reshape(1, -1) for o in outs)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(axes),),
                                 out_specs=(P(axes),) * len(OPS),
                                 check_vma=False))


def check_output(name: str, out, want: np.ndarray, mesh) -> None:
    """Bit-exact per shard, and each shard on its own rank's device."""
    shards = out.addressable_shards
    devices = list(mesh.devices.flat)
    if len({s.device for s in shards}) != len(devices):
        raise AssertionError(f"{name}: {len(shards)} shards on "
                             f"{len({s.device for s in shards})} devices")
    for s in shards:
        rank = s.index[0].start or 0
        if s.device != devices[rank]:
            raise AssertionError(f"{name}: rank {rank}'s output is on "
                                 f"{s.device}, not {devices[rank]}")
        got = np.asarray(s.data).reshape(-1)
        if not np.array_equal(got.view(np.uint32),
                              np.ascontiguousarray(want[rank]).view(
                                  np.uint32)):
            bad = int(np.flatnonzero(got != want[rank])[0])
            raise AssertionError(f"{name}: rank {rank} element {bad} is "
                                 f"{got[bad]}, expected {want[rank][bad]}")


def comm_phase(devices, sizes=SIZES, transports=TRANSPORTS,
               seed: int = 0) -> int:
    """Every op x transport x size x mesh; returns the checks passed."""
    checks = 0
    for mname, mesh in comm_meshes(devices).items():
        axes = tuple(mesh.axis_names)
        programs = {"lax": lax_program(mesh)}
        programs.update((t, comm_program(mesh, t)) for t in transports)
        for size in sizes:
            n, m = mesh.size, size // 4
            rng = np.random.default_rng(seed + size)
            x = rng.integers(-8, 9, size=(n, m)).astype(np.float32)
            xd = jax.device_put(x, NamedSharding(mesh, P(axes)))
            want = oracle(x)
            for name, fn in programs.items():
                for op, out in zip(OPS, fn(xd)):
                    check_output(f"{mname} {name} {op} {size}B", out,
                                 want[op], mesh)
                    checks += 1
            log(f"{mname} {size} B/rank: {len(OPS)} ops x "
                f"{len(transports)} transports + lax match the oracle")
    return checks


# --------------------------------------------------------------------- main


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the Communicator phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(4 if args.four_chip else 1)
    cache = enable_compile_cache()
    d = devices[0]
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"device {d.platform} {d.device_kind} x{len(devices)}; compile "
        f"cache {cache} holds {warm} entries at start")
    if args.four_chip:
        t0 = time.perf_counter()
        checks = comm_phase(devices, seed=args.seed)
        log(f"Communicator: {checks} bit-exact checks in "
            f"{time.perf_counter() - t0:.1f} s")
    else:
        serving_phase(get_config(ARCH), slots=SLOTS, max_len=MAX_LEN,
                      n_requests=N_REQUESTS, prompt_lens=PROMPT_LENS,
                      new_tokens=NEW_TOKENS, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
