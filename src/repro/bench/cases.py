"""The paper's benchmark matrix as registered cases.

    p2p            Fig 2/3   send/roundtrip size sweep + v5e link model
    multipair      OMB-Py    k simultaneous p2p pairs, aggregate GB/s
    bibw           OMB-Py    bidirectional sendrecv bandwidth
    msgrate        OMB-Py    back-to-back small-message issue rate
    overlap        Charm4Py  overlap fraction: compute + in-flight
                             allreduce vs the sum of each alone
    agg            Fig 5     tree vs native aggregation, 2..8 ranks
    bcast          Fig 7     serial/tree/native broadcast + pod-scale model
    scatter        Fig 6     scatter (per-transport bcast schedule) and
                             gather-to-nonzero-root, tree vs native
    grad_exchange  trainer   allreduce variants on the 2x2x2 pod mesh
                             with HLO link-byte accounting, plus the
                             train-step tie-in (blocking vs overlap
                             microbatch pipeline, steps.py)
    stream         HPCC      STREAM triad local-bandwidth anchor

Every measured case drives the public :class:`~repro.comms.Communicator`
surface only (OMB-Py discipline; the OMB-Py/Charm4Py-parity families
mirror arXiv:2110.10659 / arXiv:2111.04872).  jax is imported inside the
bodies: this module's *metadata* must be importable in the parent
process before any device initialization.
"""
from __future__ import annotations

from repro.bench import hw
from repro.bench.registry import BenchContext, register_case
from repro.bench.sampling import gbps


def _comm_op_fn(comm, op, spec, **kw):
    """jit a single collective through ``comm.wrap``, reducing the output
    to one tiny value per rank so timing isn't dominated by materializing
    the gathered buffer."""
    import jax

    def body(a):
        out = getattr(comm, op)(a, **kw)
        return out.reshape(1, -1).mean(1, keepdims=True)
    return jax.jit(comm.wrap(body, in_specs=(spec,), out_specs=spec))


# ------------------------------------------------------------------ p2p


@register_case("p2p", figure="fig2/3", ndev=2,
               description="point-to-point send/roundtrip size sweep "
                           "over Communicator send/recv")
def run_p2p(ctx: BenchContext):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comms import Communicator
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2,), ("x",))
    comm = Communicator(mesh)
    spec = P("x")

    def oneway(v):
        return comm.send(v, dst=1, src=0)

    def roundtrip(v):
        return comm.recv(comm.send(v, dst=1, src=0), 1, dst=0)

    for size in ctx.profile.p2p_sizes:
        n = max(size // 4, 1)
        x = jnp.zeros((2, n), jnp.float32)
        f = jax.jit(comm.wrap(oneway, in_specs=(spec,), out_specs=spec))
        g = jax.jit(comm.wrap(roundtrip, in_specs=(spec,), out_specs=spec))
        st = ctx.measure(f, x)
        yield ctx.row(f"p2p_send_{size}B", ranks=2, size_bytes=size,
                      stats=st, gbps=gbps(size, st["median_us"]))
        yield ctx.row(f"p2p_roundtrip_{size}B", ranks=2, size_bytes=size,
                      stats=ctx.measure(g, x))

    if not ctx.profile.modeled:
        return
    for size in ctx.profile.p2p_sizes:
        t_ici = hw.ICI_LAT + size / hw.ICI_BW
        t_dci = hw.DCI_LAT + size / hw.DCI_BW
        yield ctx.model_row(f"p2p_model_ici_{size}B", us=t_ici * 1e6,
                            ranks=2, size_bytes=size,
                            gbps=size / t_ici / 1e9)
        yield ctx.model_row(f"p2p_model_dci_{size}B", us=t_dci * 1e6,
                            ranks=2, size_bytes=size,
                            gbps=size / t_dci / 1e9)


# ------------------------------------- OMB-Py parity: multipair / bibw /
# msgrate (arXiv:2110.10659 §4: multi-pair bandwidth, bidirectional
# bandwidth, message rate — dimensions the paper's Fig 2/3 single-pair
# sweep does not cover)


@register_case("multipair", figure="omb:multipair", ndev=8,
               description="k simultaneous disjoint p2p pairs in one "
                           "sendrecv round; aggregate GB/s")
def run_multipair(ctx: BenchContext):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comms import Communicator
    from repro.launch.mesh import make_mesh

    n = max(ctx.ndev - ctx.ndev % 2, 2)
    mesh = make_mesh((n,), ("x",))
    comm = Communicator(mesh)
    spec = P("x")
    for k in sorted({1, 2, n // 2}):
        if k > n // 2:
            continue
        pairs = tuple((2 * i, 2 * i + 1) for i in range(k))
        for size in ctx.profile.p2p_sizes:
            x = jnp.zeros((n, max(size // 4, 1)), jnp.float32)

            def body(v, ps=pairs):
                out = comm.sendrecv(v, ps)
                return out.reshape(1, -1).mean(1, keepdims=True)
            f = jax.jit(comm.wrap(body, in_specs=(spec,), out_specs=spec))
            st = ctx.measure(f, x)
            yield ctx.row(f"multipair_k{k}_{size}B", ranks=n,
                          size_bytes=size, stats=st,
                          gbps=gbps(size * k, st["median_us"]),
                          note=f"pairs={k} aggregate")


@register_case("bibw", figure="omb:bibw", ndev=2,
               description="bidirectional bandwidth: both directions of "
                           "one pair in flight in the same round")
def run_bibw(ctx: BenchContext):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comms import Communicator
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2,), ("x",))
    comm = Communicator(mesh)
    spec = P("x")

    def body(v):
        out = comm.sendrecv(v, ((0, 1), (1, 0)))
        return out.reshape(1, -1).mean(1, keepdims=True)

    f = jax.jit(comm.wrap(body, in_specs=(spec,), out_specs=spec))
    for size in ctx.profile.p2p_sizes:
        x = jnp.zeros((2, max(size // 4, 1)), jnp.float32)
        st = ctx.measure(f, x)
        yield ctx.row(f"bibw_{size}B", ranks=2, size_bytes=size, stats=st,
                      gbps=gbps(2 * size, st["median_us"]),
                      note="2x payload in flight")


@register_case("msgrate", figure="omb:msgrate", ndev=2,
               description="back-to-back small-message issue rate: a "
                           "chained window of sends per timed call")
def run_msgrate(ctx: BenchContext):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comms import Communicator
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2,), ("x",))
    comm = Communicator(mesh)
    spec = P("x")
    window = ctx.profile.msgrate_window
    size = ctx.profile.p2p_sizes[0]

    def body(v):
        # chained (+1 defeats CSE): each hop issues only after the
        # previous returns — OMB-Py's back-to-back message discipline
        for _ in range(window):
            v = comm.send(v + 1.0, dst=1, src=0)
        return v.reshape(1, -1).mean(1, keepdims=True)

    f = jax.jit(comm.wrap(body, in_specs=(spec,), out_specs=spec))
    x = jnp.zeros((2, max(size // 4, 1)), jnp.float32)
    st = ctx.measure(f, x)
    rate = window / (st["min_us"] * 1e-6)
    yield ctx.row(f"msgrate_w{window}_{size}B", ranks=2, size_bytes=size,
                  stats=st, note=f"msgs/s={rate:.0f} window={window}")


# ------------------------------------------- Charm4Py parity: overlap


@register_case("overlap", figure="charm4py:overlap", ndev=2,
               description="overlap fraction per transport/size: an "
                           "R-slot compute+allreduce pipeline, blocking "
                           "vs double-buffered in one program")
def run_overlap(ctx: BenchContext):
    """Charm4Py's headline measurement (arXiv:2111.04872 §5.3): how much
    collective time hides behind compute when the exchange is issued a
    slot early.  Two jitted programs, each R = ``overlap_slots`` slots of
    (matmul-chain compute, allreduce):

      * ``blocking``   — slot i's allreduce operand depends on slot i's
        compute output, so every exchange serializes after its compute;
      * ``overlapped`` — the pipeline is double-buffered: slot i
        exchanges the payload produced by slot i-1, which is ready at
        slot entry, so XLA may schedule the collective alongside the
        matmuls (rendezvous/dispatch hiding even without spare cores).

    Same compute, same R collectives of the same size; the fraction

        frac = (t_blocking - t_overlapped) / t_coll_only

    (best-of-N, t_coll_only = R chained allreduces alone) is the share
    of total collective time the restructuring recovers: 0 = none,
    1 = fully hidden.  This is the microbenchmark form of the train
    step's ``*_overlap`` grad-exchange pipeline (train/steps.py), and
    the R-slot repetition keeps the timed region in the multi-ms range
    where best-of-N is stable on an oversubscribed host.  Pair scale
    (ndev=2) on purpose: overlap is a per-link property, and more
    virtual ranks on one host only add rendezvous jitter."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comms import Communicator
    from repro.launch.mesh import make_mesh

    n = ctx.ndev
    mesh = make_mesh((n,), ("x",))
    spec = P("x")
    d = ctx.profile.overlap_compute_dim
    reps = ctx.profile.overlap_compute_iters
    slots = max(ctx.profile.overlap_slots, 2)

    def chain(z, w):
        for _ in range(reps):
            z = jnp.tanh(z @ w)
        return z

    z0 = jnp.ones((n, d, d), jnp.float32)
    w0 = jnp.ones((d, d), jnp.float32) * 0.01
    sizes = sorted(set(ctx.profile.overlap_sizes))
    for tname in ("native", "tree", "hier"):
        comm = Communicator(mesh, tname)

        def coll_only(v):
            # R chained exchanges (+1 defeats CSE): total collective time
            for _ in range(slots):
                v = comm.allreduce(v + 1.0) / n
            return v.reshape(1, -1).mean(1, keepdims=True)

        def blocking(v, z, w):
            # slot i's payload derives from slot i's compute: the
            # exchange cannot start until the matmul chain retires
            acc = jnp.zeros((1, 1), jnp.float32)
            for _ in range(slots):
                z = chain(z, w)
                payload = v + z[0, :1, :1]
                acc = acc + comm.allreduce(payload).mean()
            return acc / slots

        def overlapped(v, z, w):
            # double-buffered: slot i exchanges slot i-1's payload,
            # ready at slot entry — same compute, same R collectives
            acc = jnp.zeros((1, 1), jnp.float32)
            z = chain(z, w)
            pending = v + z[0, :1, :1]
            for _ in range(slots - 1):
                acc = acc + comm.allreduce(pending).mean()
                z = chain(z, w)
                pending = v + z[0, :1, :1]
            acc = acc + comm.allreduce(pending).mean()   # drain
            return acc / slots

        for size in sizes:
            x = jnp.ones((n, max(size // 4, 1)), jnp.float32)
            f_coll = jax.jit(comm.wrap(coll_only, in_specs=(spec,),
                                       out_specs=spec))
            f_blk = jax.jit(comm.wrap(blocking, in_specs=(spec, spec, P()),
                                      out_specs=P()))
            f_ovl = jax.jit(comm.wrap(overlapped,
                                      in_specs=(spec, spec, P()),
                                      out_specs=P()))
            from repro.bench.sampling import sample_paired, stats_us
            st_coll = ctx.measure(f_coll, x)
            # interleave blocking/overlapped samples so host drift hits
            # both equally and the best-of-N difference stays meaningful
            s_blk, s_ovl = sample_paired(
                f_blk, (x, z0, w0), f_ovl, (x, z0, w0),
                warmup=ctx.profile.warmup, iters=ctx.profile.iters)
            st_blk, st_ovl = stats_us(s_blk), stats_us(s_ovl)
            frac = ((st_blk["min_us"] - st_ovl["min_us"])
                    / max(st_coll["min_us"], 1e-9))
            yield ctx.row(
                f"overlap_{tname}_{size}B", transport=tname, ranks=n,
                size_bytes=size, stats=st_ovl,
                note=f"frac={frac:.3f} blocking_us={st_blk['min_us']:.0f} "
                     f"coll_us={st_coll['min_us']:.0f} slots={slots}")


# ----------------------------------------------------------- agg / bcast


def _rank_sweep(ctx: BenchContext):
    """(mesh, comms, spec, n) per rank count, transports shared."""
    from jax.sharding import PartitionSpec as P

    from repro.comms import Communicator
    from repro.launch.mesh import make_mesh

    for n in ctx.rank_counts():
        mesh = make_mesh((n,), ("r",))
        comms = {name: Communicator(mesh, name)
                 for name in ("native", "tree", "serial")}
        yield n, comms, P("r")


def _per_rank_input(n: int, size: int):
    import jax.numpy as jnp
    return jnp.ones((n, max(size // 4, 1)), jnp.float32)


@register_case("agg", figure="fig5", ndev=8,
               description="aggregation: paper tree gather vs native "
                           "all-gather, 2..8 ranks x per-rank sizes")
def run_agg(ctx: BenchContext):
    for n, comms, spec in _rank_sweep(ctx):
        for size in ctx.profile.coll_sizes:
            x = _per_rank_input(n, size)
            for tname in ("tree", "native"):
                st = ctx.measure(_comm_op_fn(comms[tname], "agg", spec), x)
                yield ctx.row(f"agg_{tname}_r{n}_{size}B", transport=tname,
                              ranks=n, size_bytes=size, stats=st)


@register_case("bcast", figure="fig7", ndev=8,
               description="broadcast: serial (paper initial) vs tree "
                           "(optimized) vs native, plus pod-scale model")
def run_bcast(ctx: BenchContext):
    for n, comms, spec in _rank_sweep(ctx):
        for size in ctx.profile.coll_sizes:
            x = _per_rank_input(n, size)
            for tname in ("tree", "serial", "native"):
                st = ctx.measure(_comm_op_fn(comms[tname], "bcast", spec), x)
                yield ctx.row(f"bcast_{tname}_r{n}_{size}B",
                              transport=tname, ranks=n, size_bytes=size,
                              stats=st)

    if not ctx.profile.modeled:
        return
    # Fig 7 extension: two-level model at pod scale (in-pod 256 ranks on
    # ICI, cross-pod on DCI)
    from repro.core import topology

    for total in (64, 256, 512, 768):
        n_local = min(total, 256)
        n_global = max(total // 256, 1)
        for size in ctx.profile.coll_sizes:
            t_tree = topology.two_level_cost(n_local, n_global, size,
                                             hw.ICI_BW, hw.DCI_BW,
                                             tree=True)
            t_serial = topology.two_level_cost(n_local, n_global, size,
                                               hw.ICI_BW, hw.DCI_BW,
                                               tree=False)
            yield ctx.model_row(
                f"bcast_model_tree_r{total}_{size}B", us=t_tree * 1e6,
                transport="tree", ranks=total, size_bytes=size,
                note=f"speedup={t_serial / max(t_tree, 1e-12):.1f}x")
            yield ctx.model_row(
                f"bcast_model_serial_r{total}_{size}B", us=t_serial * 1e6,
                transport="serial", ranks=total, size_bytes=size)


# ------------------------------------------------------ scatter / gather


@register_case("scatter", figure="fig6", ndev=8,
               description="scatter (root distributes blocks; schedule "
                           "follows the transport's bcast) and gather to "
                           "a non-zero root")
def run_scatter(ctx: BenchContext):
    for n, comms, spec in _rank_sweep(ctx):
        for size in ctx.profile.coll_sizes:
            x = _per_rank_input(n, size)
            for tname in ("tree", "serial", "native"):
                st = ctx.measure(
                    _comm_op_fn(comms[tname], "scatter", spec), x)
                yield ctx.row(f"scatter_{tname}_r{n}_{size}B",
                              transport=tname, ranks=n, size_bytes=size,
                              stats=st)
            # gather-to-root at the far end of the rank line (root=n-1):
            # exercises the rotated tree schedule, the Fig 6 direction
            # the agg case (root=0) does not cover
            for tname in ("tree", "native"):
                st = ctx.measure(
                    _comm_op_fn(comms[tname], "agg", spec, root=n - 1), x)
                yield ctx.row(f"gather_root{n - 1}_{tname}_r{n}_{size}B",
                              transport=tname, ranks=n, size_bytes=size,
                              stats=st)


# ---------------------------------------------------- alltoall / MoE


@register_case("alltoall", figure="fig3+moe", ndev=8,
               description="all-to-all message-size sweep across "
                           "transports, ragged alltoallv, and "
                           "expert-parallel MoE dispatch tokens/sec")
def run_alltoall(ctx: BenchContext):
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh

    # --- message-size sweep (the Fig 2/3 discipline applied to the
    # routed-exchange collective OMB-Py benchmarks as a core family)
    for n, comms, spec in _rank_sweep(ctx):
        for size in ctx.profile.coll_sizes:
            elems = max(size // 4, n)
            elems -= elems % n
            x = jnp.ones((n, elems), jnp.float32)
            for tname in ("native", "tree", "serial"):
                comm = comms[tname]

                def body(a, c=comm, nn=n):
                    out = c.alltoall(a.reshape(nn, -1))
                    return out.reshape(1, -1).mean(1, keepdims=True)
                f = jax.jit(comm.wrap(body, in_specs=(spec,),
                                      out_specs=spec))
                st = ctx.measure(f, x)
                yield ctx.row(f"alltoall_{tname}_r{n}_{size}B",
                              transport=tname, ranks=n, size_bytes=size,
                              stats=st,
                              gbps=gbps(size, st["median_us"]))
        # ragged exchange: one alltoallv row per rank count at the
        # mid-profile size, asymmetric static count matrix
        size = ctx.profile.coll_sizes[len(ctx.profile.coll_sizes) // 2]
        base = max(size // 4 // n, 1)
        counts = [[base * ((i + 2 * j) % 3 + 1) for j in range(n)]
                  for i in range(n)]
        S = max(sum(r) for r in counts)
        xv = jnp.ones((n, S), jnp.float32)
        for tname in ("native", "tree"):
            comm = comms[tname]

            def bodyv(a, c=comm, cnt=counts, s=S):
                out = c.alltoallv(a.reshape(s, 1), cnt)
                return out.reshape(1, -1).mean(1, keepdims=True)
            f = jax.jit(comm.wrap(bodyv, in_specs=(spec,),
                                  out_specs=spec))
            st = ctx.measure(f, xv)
            yield ctx.row(f"alltoallv_{tname}_r{n}_{size}B",
                          transport=tname, ranks=n, size_bytes=size,
                          stats=st)

    # --- MoE expert-parallel dispatch at model scale: two alltoalls
    # (dispatch + combine) per step through the same Communicator
    from repro.models.moe import moe_ffn, moe_init

    pr = ctx.profile
    m = 1 << (ctx.ndev.bit_length() - 1)        # model-axis power of two
    mesh = make_mesh((1, m), ("data", "model"))
    E = max(pr.moe_experts // m, 1) * m
    T = max(pr.moe_tokens // m, 1) * m
    key = jax.random.PRNGKey(0)
    params = moe_init(key, pr.moe_d_model, pr.moe_d_ff, E)
    x = jax.random.normal(key, (1, T, pr.moe_d_model), jnp.bfloat16)
    for tname in ("native", "tree"):
        f = jax.jit(lambda p, v, t=tname: moe_ffn(
            p, v, top_k=pr.moe_top_k, num_experts=E,
            capacity_factor=2.0, mesh=mesh, batch_axes=("data",),
            mode="scatter", comm=t)[0])
        st = ctx.measure(f, params, x)
        toks = T / (st["median_us"] * 1e-6)
        yield ctx.row(f"moe_dispatch_{tname}_t{T}", transport=tname,
                      ranks=m, size_bytes=T * pr.moe_d_model * 2,
                      stats=st, note=f"tok/s={toks:.0f}")


# -------------------------------------------------------- grad exchange


@register_case("grad_exchange", figure="trainer", ndev=8,
               description="gradient allreduce variants on the pod mesh "
                           "with HLO link-byte accounting, plus the "
                           "blocking-vs-overlap train-step tie-in")
def run_grad_exchange(ctx: BenchContext):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comms import CommSpec, Communicator
    from repro.launch.mesh import make_mesh
    from repro.roofline import hlo as hlo_lib

    if ctx.ndev >= 8:
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        axes, pod_size, n_pods = ("pod", "data"), 4, 2
    else:  # tiny/test budget: batch-axis-only exchange, no pod level
        mesh = make_mesh((ctx.ndev,), ("data",))
        axes, pod_size, n_pods = ("data",), ctx.ndev, 1
    ranks = ctx.ndev if ctx.ndev < 8 else 8
    nbytes = ctx.profile.gradex_bytes
    x = jnp.ones((ranks, max(nbytes // 4 // ranks, 1)), jnp.float32)
    spec = P(tuple(mesh.axis_names))

    for name in ("native", "tree", "hier", "hier_int8"):
        comm = Communicator(mesh, CommSpec.from_flag(name), axes=axes)
        f = jax.jit(comm.wrap(comm.allreduce, in_specs=(spec,),
                              out_specs=spec))
        st = ctx.measure(f, x)
        an = hlo_lib.analyze(f.lower(x).compile().as_text(),
                             pod_size=pod_size, n_pods=n_pods)
        yield ctx.row(
            f"gradex_{name}_{nbytes}B", transport=name, ranks=ranks,
            size_bytes=nbytes, stats=st,
            note=f"link={an.get('link_bytes', 0.0) / 2 ** 20:.2f}MiB "
                 f"dci={an.get('dci_link_bytes', 0.0) / 2 ** 20:.2f}MiB")

    # --- train-step tie-in: the same exchange inside the real
    # microbatched step (train/steps.py), blocking scan vs the
    # one-slot-deep overlap pipeline — the row pair the `overlap`
    # microbenchmark case predicts
    from jax.sharding import NamedSharding

    from repro.configs.base import ShapeSpec, get_config, reduced
    from repro.launch.mesh import make_local_mesh
    from repro.models.model import Model
    from repro.optim.optimizer import OptimizerConfig, opt_init
    from repro.train import steps as steps_lib

    pr = ctx.profile
    cfg = reduced(get_config("h2o-danube-1.8b"),
                  microbatches=pr.gradex_step_mb)
    shape = ShapeSpec("bench", "train", pr.gradex_step_seq,
                      pr.gradex_step_batch)
    tmesh = (make_local_mesh(2, 2, pod=2) if ctx.ndev >= 8
             else make_local_mesh(ctx.ndev, 1))
    model = Model(cfg, tmesh)
    ocfg = OptimizerConfig()
    bundle = steps_lib.sharding_bundle(model, ocfg, shape)
    params = jax.jit(model.init,
                     out_shardings=bundle["params"])(jax.random.PRNGKey(0))
    opt = jax.jit(lambda p: opt_init(ocfg, p),
                  out_shardings=bundle["opt"])(params)
    toks = jax.random.randint(
        jax.random.PRNGKey(1),
        (pr.gradex_step_batch, pr.gradex_step_seq), 0, cfg.vocab_size)
    batch = jax.device_put({"tokens": toks, "labels": toks},
                           bundle["input_shardings"])
    step0 = jnp.zeros((), jnp.int32)
    gbytes = 4 * sum(p.size for p in jax.tree.leaves(params))
    for mode in ("tree", "tree_overlap"):
        step_fn, mbn = steps_lib.make_train_step(
            model, ocfg, shape.global_batch, grad_comms=mode)
        f = jax.jit(step_fn,
                    in_shardings=(bundle["params"], bundle["opt"],
                                  bundle["input_shardings"],
                                  NamedSharding(tmesh, P())),
                    out_shardings=(bundle["params"], bundle["opt"], None))
        st = ctx.measure(f, params, opt, batch, step0)
        label = "overlap" if mode.endswith("_overlap") else "blocking"
        yield ctx.row(f"gradex_step_{label}_tree", transport="tree",
                      ranks=ctx.ndev, size_bytes=gbytes, stats=st,
                      note=f"mb={mbn} batch={pr.gradex_step_batch} "
                           f"seq={pr.gradex_step_seq}")


# --------------------------------------------------------- compression


@register_case("compression", figure="fig3", ndev=8,
               description="wire vs effective GB/s for the compressed "
                           "allreduce: each quantization dtype composed "
                           "with the tree/hier transports")
def run_compression(ctx: BenchContext):
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comms import CommSpec, Communicator, CompressionSpec
    from repro.launch.mesh import make_mesh

    if ctx.ndev >= 8:
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        axes = ("pod", "data")
    else:  # tiny/test budget: batch-axis-only exchange, no pod level
        mesh = make_mesh((ctx.ndev,), ("data",))
        axes = ("data",)
    ranks = ctx.ndev if ctx.ndev < 8 else 8
    spec = P(tuple(mesh.axis_names))
    # cross-pod dominates a hierarchical exchange, so scope the wire
    # quantization there — exactly what `--grad-comms tree_int8` runs
    for size in ctx.profile.compress_sizes:
        n = max(size // 4 // ranks, 1)          # f32 elements per rank
        x = jnp.ones((ranks, n), jnp.float32)
        logical = 4 * n                          # per-rank payload, bytes
        for tname in ("tree", "hier"):
            base = CommSpec.from_flag(tname)
            for dtype in (None, "int8", "fp8", "int4"):
                if dtype is None:
                    cs, cspec, label = base, None, "none"
                else:
                    cspec = CompressionSpec(dtype=dtype, scope="cross-pod")
                    cs = dataclasses.replace(base, compression=cspec)
                    label = dtype
                comm = Communicator(mesh, cs, axes=axes)
                f = jax.jit(comm.wrap(comm.allreduce, in_specs=(spec,),
                                      out_specs=spec))
                st = ctx.measure(f, x)
                eff = gbps(logical, st["median_us"])
                if cspec is None:
                    wire, note = eff, "uncompressed"
                else:
                    wb = cspec.wire_bytes(n)
                    wire = gbps(wb, st["median_us"])
                    note = f"ratio={cspec.ratio(n):.2f}x"
                yield ctx.row(
                    f"compress_{tname}_{label}_{size}B", transport=tname,
                    ranks=ranks, size_bytes=size, stats=st, gbps=eff,
                    wire_gbps=wire, effective_gbps=eff, note=note)


# -------------------------------------------------------------- stream


@register_case("stream", figure="hpcc", ndev=1,
               description="HPCC STREAM triad local-bandwidth anchor")
def run_stream(ctx: BenchContext):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def triad(b, c):
        return b + 3.0 * c

    for n in ctx.profile.stream_sizes:
        b = jnp.ones((n,), jnp.float32)
        c = jnp.ones((n,), jnp.float32)
        st = ctx.measure(triad, b, c)
        nbytes = 3 * 4 * n
        yield ctx.row(f"stream_triad_{n}", ranks=1, size_bytes=nbytes,
                      stats=st, gbps=gbps(nbytes, st["median_us"]))
