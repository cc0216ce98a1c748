"""Elastic families: resharding bandwidth and detect-to-resume time.

``redistribute`` times the capability pMatlab/pPython name as the
library's core — moving a distributed array between two maps — both
ways we implement it:

    redist_stream_<pair>_<t>   streamed Communicator.redistribute (one
                               scheduled Alltoallv from the static
                               (counts, send, recv) plan) over
                               transport ``<t>``;
    redist_gather_<pair>       the composed-static-gather reference
                               (GSPMD emits the communication).

Rows carry the global array bytes and derived GB/s — resharding
bandwidth is a figure no related repo publishes.

``recovery`` runs the RecoverySupervisor under an armed FaultPlan whose
schedule kills half the devices mid-run (shrink remesh + checkpoint
restore + replay) and later restores them (grow remesh + LIVE state
redistribution, no checkpoint round-trip), and reports each event's
**detect-to-resume** seconds: exception observed -> first step
completed on the new mesh (includes the re-jit, which is honest for
this container).
"""
from __future__ import annotations

from repro.bench.registry import BenchContext, register_case

ARCH = "h2o-danube-1.8b"


def _map_pairs(n: int, shape):
    """(label, src, dst) Dmap pairs adapted to ``n`` ranks — at least
    two distinct layout changes, incl. a block-cyclic+overlap target."""
    from repro.core.dmap import Dmap

    pairs = [
        ("rowcol", Dmap(grid=(n, 1)), Dmap(grid=(1, n))),
        ("bc_ov", Dmap(grid=(n, 1)),
         Dmap(grid=(n, 1), dist=(("bc", 2), ("b",)), overlap=(1, 0))),
    ]
    if n >= 4:
        pairs.append(("grid", Dmap(grid=(n // 2, 2)),
                      Dmap(grid=(2, n // 2), dist=(("c",), ("b",)))))
    return pairs


@register_case("redistribute", figure="elastic", ndev=8,
               description="Dmap-to-Dmap resharding GB/s: streamed "
                           "Alltoallv plan vs composed-gather reference")
def run_redistribute(ctx: BenchContext):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.bench.sampling import gbps
    from repro.comms import Communicator
    from repro.core import dmat
    from repro.core.dmap import redistribution_plan
    from repro.launch.mesh import make_mesh

    n = max(ctx.ndev, 2)
    shape = tuple(ctx.profile.redist_shape)
    size_bytes = 4
    for s in shape:
        size_bytes *= s
    mesh = make_mesh((n,), ("r",))
    arr = jnp.arange(float(shape[0] * shape[1]),
                     dtype=jnp.float32).reshape(shape)

    for label, src, dst in _map_pairs(n, shape):
        d = dmat.Dmat.from_global(arr, src, mesh)
        counts, _, _ = redistribution_plan(src, dst, shape, n)
        wire = int(counts.sum()) * 4
        for tname in ("native", "tree"):
            comm = Communicator(mesh, tname, axes=("r",))

            def body(block, c=comm, s=src, t=dst):
                return c.redistribute(block, s, t, shape)

            fn = jax.jit(comm.wrap(body, in_specs=(P("r"),),
                                   out_specs=P("r")))
            st = ctx.measure(fn, d.storage)
            yield ctx.row(f"redist_stream_{label}_{tname}",
                          transport=tname, ranks=n, size_bytes=size_bytes,
                          stats=st,
                          gbps=gbps(size_bytes, st["median_us"]),
                          note=f"wire_bytes={wire} shape={shape}")

        def gather_fn(storage, s=src, t=dst):
            return dmat.Dmat(storage, s, shape, mesh).redistribute(
                t, method="gather").storage

        fng = jax.jit(gather_fn)
        st = ctx.measure(fng, d.storage)
        yield ctx.row(f"redist_gather_{label}", transport="gspmd",
                      ranks=n, size_bytes=size_bytes, stats=st,
                      gbps=gbps(size_bytes, st["median_us"]),
                      note=f"shape={shape}")


@register_case("recovery", figure="elastic", ndev=8,
               description="detect-to-resume seconds across a "
                           "lose/shrink and a restore/grow event")
def run_recovery(ctx: BenchContext):
    import tempfile

    from repro.bench.sampling import stats_us
    from repro.comms import faults
    from repro.configs.base import ShapeSpec, get_config, reduced
    from repro.train.recovery import RecoveryConfig, RecoverySupervisor
    from repro.train.trainer import TrainerConfig

    n = max(ctx.ndev, 2)
    steps = max(ctx.profile.recovery_steps, 4)
    lose_step, restore_step = steps // 2, steps - 1
    plan = faults.FaultPlan(events=(
        faults.HostEvent(lose_step, faults.LOSE, max(n // 2, 1)),
        faults.HostEvent(restore_step, faults.RESTORE, n)))

    cfg = reduced(get_config(ARCH))
    shape = ShapeSpec("bench", "train", 16, 8)
    sup = RecoverySupervisor(
        cfg, shape,
        TrainerConfig(total_steps=steps, checkpoint_every=2,
                      ckpt_dir=tempfile.mkdtemp(prefix="bench_recovery_"),
                      log_every=10 ** 9),
        RecoveryConfig(model_width=1))
    with faults.armed(plan):
        out = sup.run(n_devices=n)
    assert out["recoveries"] == 2, out["events"]
    shrink_s, grow_s = out["detect_to_resume_s"]
    yield ctx.row("recovery_shrink_resume", ranks=n, size_bytes=0,
                  stats=stats_us([shrink_s]),
                  note=f"lose {n}->{max(n // 2, 1)} at step {lose_step}; "
                       f"ckpt restore + replay")
    yield ctx.row("recovery_grow_resume", ranks=n, size_bytes=0,
                  stats=stats_us([grow_s]),
                  note=f"restore ->{n} at step {restore_step}; "
                       f"live redistribute, no ckpt round-trip")
