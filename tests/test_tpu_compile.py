"""Compile rehearsals for a described TPU v5e chip, at the widths the main
path gives each program: nothing runs, but the TPU compiler refuses here
what it would refuse on the chip (block shapes that do not match XLA's
tiling, Mosaic primitives it cannot lower, a missing collective).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file. The fixture skips where no v5e
topology can be described.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import (LeafSpine, SimConfig, compile_routes,
                        default_law_config, leaf_spine_fabric, make_schedule,
                        poisson_websearch, resolve_devices,
                        schedule_as_flows, suggest_slots)
from repro.core import fluid, megakernel, shardslots
from repro.core.fluid import SlotSim, _host_window
from repro.kernels.powertcp_step import powertcp_step, theta_powertcp_step
from repro.kernels.queue_arrivals import queue_arrivals

DT = 1e-6
F_PAPER = 4842        # flows of the 256-host leaf-spine, 60% load, 2 seeds
H_LEAFSPINE = 3       # host-up, spine-down, host-down queued hops
Q_LEAFSPINE = 288     # 256-host leaf-spine queues (DESIGN.md section 13)
H_FATTREE = 5         # inter-pod fat-tree path (core/fabric.py fat_tree)
Q_FATTREE = 5120      # k=16 fat-tree queues
S_FATTREE = 1024      # k=16 slot pool of the sharded headline scenario


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kernel", ["powertcp_step", "theta_powertcp_step"])
def test_powertcp_kernels_compile_for_v5e(one_chip, kernel):
    F, H = F_PAPER, H_LEAFSPINE
    f = _on(one_chip, (F,))
    m = _on(one_chip, (F,), jnp.bool_)
    if kernel == "powertcp_step":
        h = _on(one_chip, (F, H))
        args = (h, h, h, h, _on(one_chip, (F, H), jnp.bool_),
                f, f, f, f, f, m, f)
        fn = lambda *a: powertcp_step(*a, interpret=False)
    else:
        args = (f, f, f, f, f, f, f, m, f)
        fn = lambda *a: theta_powertcp_step(*a, interpret=False)
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


@pytest.mark.parametrize("flows,hops,queues", [
    (F_PAPER, H_LEAFSPINE, Q_LEAFSPINE),
    (S_FATTREE, H_FATTREE, Q_FATTREE),
], ids=["leafspine256", "fattree_k16"])
def test_queue_arrivals_compiles_for_v5e(one_chip, flows, hops, queues):
    q = _on(one_chip, (queues + 1,))          # + the sentinel queue
    c = _compile(lambda *a: queue_arrivals(*a, dt=DT, interpret=False),
                 _on(one_chip, (hops, flows)),
                 _on(one_chip, (hops, flows, queues + 1)), q, q, q)
    assert "tpu_custom_call" in c.as_text()


def test_megakernel_block_compiles_through_xla_for_v5e(one_chip):
    """The megakernel's K-tick block at the 256-host pool size, through
    plain XLA (the lowering ``default_impl`` picks on TPU)."""
    fab = LeafSpine(racks=8, hosts_per_rack=32, spines=2)   # the paper's
    sched = make_schedule(poisson_websearch(fab, 0.6, 0.03, DT, seed=1))
    S = suggest_slots(sched, DT)
    cfg = SimConfig(dt=DT, steps=40_000, hist=512, update_period=2e-6)
    lcfg = default_law_config(schedule_as_flows(sched), expected_flows=8.0)
    law = fluid._resolve_law("powertcp", "megakernel")
    sim = SlotSim(fab.topology(), sched, law, lcfg, cfg, S, "megakernel")
    tick = megakernel.make_tick(sim, None, gate=True)
    carry = jax.eval_shape(
        lambda: tick.init_carry(fluid.init_slot_state(sim)))
    carry = jax.tree_util.tree_map(
        lambda s: _on(one_chip, s.shape, s.dtype), carry)
    block = megakernel.make_block_fn(tick, record=False)
    c = _compile(block, carry, _on(one_chip, (256,), jnp.int32))
    assert c.memory_analysis().temp_size_in_bytes < 16 * 2**30


def test_sharded_tick_compiles_on_v5e_2x2_mesh(topo):
    """The sharded slot tick at the 256-host anchor on a described 2x2
    mesh: the halo exchange must lower to an all-to-all."""
    fab = compile_routes(leaf_spine_fabric(racks=8, hosts_per_rack=32,
                                           spines=2))
    sched = make_schedule(poisson_websearch(fab, 0.3, 0.0012, DT, seed=11))
    S = -(-suggest_slots(sched, DT) // 8) * 8
    cfg = SimConfig(dt=DT, steps=3000, hist=512, update_period=2e-6)
    lcfg = default_law_config(schedule_as_flows(sched), expected_flows=8.0)
    topo_sim = fab.topology()
    law = fluid._resolve_law("powertcp", "reference")
    sim = SlotSim(topo_sim, sched, law, lcfg, cfg, S, "reference")
    sched_np = jax.tree_util.tree_map(np.asarray, sched)
    N, Q = int(sched_np.start.shape[0]), int(topo_sim.num_queues)
    mi = shardslots._shard_geometry(sched_np, S, Q, 4)
    assert mi.use_csr
    mesh = Mesh(np.array(topo.devices[:4]), (shardslots._AX,))
    init, get_seg = shardslots._sharded_programs(sim, mi, mesh, None, False)
    rep = NamedSharding(mesh, P())
    win = jax.tree_util.tree_map(lambda x: _on(rep, x.shape, x.dtype),
                                 _host_window(sched_np, 0, N, Q))
    w0 = _on(rep, (), jnp.int32)
    carry = jax.eval_shape(init, win, w0)
    text = get_seg(cfg.steps).lower(carry, win, w0).compile().as_text()
    assert "all-to-all" in text
    # the tick's phase scopes survive the TPU compiler as op metadata;
    # every halo all-to-all sits in the ``halo`` scope
    for phase in ("admit", "rates", "queue", "observe", "law", "progress",
                  "halo"):
        assert f"/{phase}/" in text, phase
    a2a = [ln for ln in text.splitlines() if " all-to-all(" in ln]
    assert a2a and all("/halo/" in ln for ln in a2a)


def test_resolve_devices_never_clamps():
    n = jax.local_device_count()
    with pytest.raises(ValueError, match="requested"):
        resolve_devices(n + 7)
    assert resolve_devices("auto") == n
    assert resolve_devices(None) == 1
    assert resolve_devices(1) == 1


def test_megakernel_lowers_through_xla_everywhere(monkeypatch):
    assert megakernel.default_impl() == "xla"
    # on TPU the Pallas whole-tick harness is refused before it compiles
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="pallas"):
        megakernel.simulate_slots_mega(None, impl="pallas")
