"""Compile the chip's main programs for a described TPU v5e, no chip
needed: the h2o-danube-1.8b serving tick at published width and depth
on one chip (it must fit the chip's 16 GB), and Communicator collectives
of 8 MB per rank on a 2x2 mesh.  The TPU compiler refuses here what the
chip would refuse: tiling, VMEM, out-of-memory.

The topology is described inside a module fixture (never at import), so
under pytest-xdist only the worker that runs this file loads the TPU
library.
"""
import os

import pytest

HBM_BYTES = 16e9                 # TPU v5e: 16 GB of HBM per chip
PER_RANK = 8 << 20               # collective payload, bytes per rank


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # noqa: BLE001 - any failure skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def danube_engine():
    from repro.configs.base import get_config
    from repro.launch.mesh import mesh_for_devices
    from repro.serve import Engine

    import chip_smoke
    cfg = get_config(chip_smoke.ARCH)
    return Engine(cfg, mesh_for_devices(1), slots=chip_smoke.SLOTS,
                  max_len=chip_smoke.MAX_LEN)


def _bytes_on_device(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.fixture(scope="module", params=["chunk", "decode"])
def danube_tick(request, one_chip, danube_engine):
    """(tick kind, the danube tick program compiled for one v5e, the
    paged pool's shape); compiled once per kind for this module."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import split_btab

    eng = danube_engine
    B = eng.slots
    width = eng.sched.chunk if request.param == "chunk" else 1

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache, btab = split_btab(eng.model.cache_specs(
        B, eng.max_len, paged=eng.page_spec))
    assert eng.paged_entries and eng.cfg.num_layers == 24
    compiled = eng._dispatch_fn.lower(
        shaped(eng.model.init_abstract()), arr((B, width), jnp.int32),
        arr((B,), jnp.bool_), arr((B,), jnp.int32), arr((B,), jnp.int32),
        arr((B,), jnp.float32), arr((2,), jnp.uint32), arr((B,), jnp.int32),
        shaped(cache), shaped(btab)).compile()
    pool = cache[eng.paged_entries[0]]["k"].shape
    return request.param, compiled, pool


def test_danube_serve_tick_fits_one_v5e(danube_tick):
    tick, compiled, _ = danube_tick
    used = _bytes_on_device(compiled)
    assert 0 < used < HBM_BYTES, f"{tick} tick needs {used / 1e9:.2f} GB"


def _pool_moves(hlo: str, pool) -> list:
    """Instructions of ``hlo`` that slice, update or copy a whole layer's
    pool or the whole stacked pool, (count, num_blocks, bs, *row), as it
    is or with its leading axes merged."""
    import re

    count, nb, bs, row = pool[0], pool[1], pool[2], tuple(pool[3:])
    shapes = {"bf16[" + ",".join(map(str, s)) + "]"
              for n in (1, count)
              for s in ((n, nb, bs) + row, (n * nb, bs) + row,
                        (n * nb * bs,) + row)}
    found = []
    for name, shape, op in re.findall(
            r"%(\S+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(", hlo):
        moves = any(w in name or w in op for w in
                    ("dynamic-slice", "dynamic-update-slice", "copy"))
        if moves and shape in shapes:
            found.append(f"{name} = {shape} {op}")
    return found


def test_danube_tick_keeps_the_paged_pool_in_place(danube_tick):
    """The layer scan carries the stacked pool and updates it in place:
    no layer's pool is sliced out or written back, and the donated pool
    is not copied, inside the loop or around it."""
    tick, compiled, pool = danube_tick
    assert len(pool) >= 4 and pool[0] == 24
    moves = _pool_moves(compiled.as_text(), pool)
    assert not moves, f"{tick} tick moves the pool: {moves}"


@pytest.mark.parametrize("transport,op", [("native", "allreduce"),
                                          ("tree", "allreduce"),
                                          ("native", "alltoall")])
def test_collective_8mb_compiles_for_2x2(transport, op, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.comms import Communicator
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("pod", "data"), devices=topo.devices)
    comm = Communicator(mesh, transport)
    spec = P(comm.axes)
    fn = jax.jit(comm.wrap(getattr(comm, op), in_specs=(spec,),
                           out_specs=spec))
    x = jax.ShapeDtypeStruct((mesh.size * (PER_RANK // 4),), jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    compiled = fn.lower(x).compile()
    assert 0 < _bytes_on_device(compiled) < HBM_BYTES
    hlo = compiled.as_text()
    want = {"allreduce": ("all-reduce" if transport == "native"
                          else "collective-permute"),
            "alltoall": "all-to-all"}[op]
    assert want in hlo
