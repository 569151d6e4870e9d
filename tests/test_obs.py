"""Host spans and counters of the slot engines (``core/obs.py``).

Spans land in a CPU profile under their ``repro.`` names with their
arguments as stats; counters take Python ints and device scalars, sum
the latter only when read (no device-to-host transfer while counting),
and are cleared by ``reset``. The engines count the calls, ticks and
chunk segments they run, and the sharded engine the ticks that took its
full-gather fallback — checked on four virtual devices against a plain
tick-by-tick count, with FCTs bit-identical to a run without spans.
"""
import glob
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (SimConfig, default_law_config, make_flows_single,
                        make_schedule, obs, schedule_as_flows, simulate_slots,
                        single_bottleneck)
from repro.core import fluid

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    yield
    obs.reset()


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    return [(e.name, dict(e.stats)) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name.startswith("repro.")]


def test_span_names_and_args_in_a_cpu_profile(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones(32)
    f(x).block_until_ready()
    with obs.span("schedule.build"):          # no profiler: not recorded
        f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("slots.call", program="run", ticks=5):
        with obs.span("chunk.sync"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    assert [n for n, _ in ev] == ["repro.slots.call", "repro.chunk.sync"]
    assert ev[0][1]["program"] == "run"
    assert int(ev[0][1]["ticks"]) == 5


def test_counters_sum_ints_and_device_scalars_lazily():
    obs.count("a")
    obs.count("a", 4)
    with jax.transfer_guard_device_to_host("disallow"):
        for i in range(3 * obs._FOLD + 5):      # folds on the device
            obs.count("b", jnp.asarray(i, jnp.int32))
        obs.count("a", jnp.asarray(2, jnp.int32))
    n = 3 * obs._FOLD + 5
    assert obs.counters() == {"a": 7, "b": n * (n - 1) // 2}
    assert isinstance(obs.counters()["b"], int)


def test_reset_clears_every_total():
    obs.count("x", 3)
    obs.count("y", jnp.asarray(1))
    obs.reset()
    assert obs.counters() == {}
    obs.count("x")
    assert obs.counters() == {"x": 1}


def _small(steps=700):
    topo = single_bottleneck(bandwidth=100e9 / 8, buffer=16e6)
    rng = np.random.default_rng(0)
    n = 10
    flows = make_flows_single(n, tau=20e-6, nic=100e9 / 8,
                              sizes=rng.uniform(8e4, 4e5, n),
                              starts=rng.uniform(0.0, 4e-4, n), sim_dt=1e-6)
    sched = make_schedule(flows)
    lcfg = default_law_config(schedule_as_flows(sched), expected_flows=8.0)
    return topo, sched, lcfg, SimConfig(dt=1e-6, steps=steps, hist=256)


def test_slot_engine_counts_calls_ticks_and_segments():
    topo, sched, lcfg, cfg = _small()
    fluid._slot_programs.clear()                  # a cold program cache
    whole, _ = simulate_slots(topo, sched, "powertcp", 8, lcfg, cfg,
                              record=False)
    assert obs.counters() == {"slots.calls": 1, "slots.ticks": cfg.steps,
                              "slots.program_lookups": 1,
                              "slots.program_misses": 1}
    obs.reset()
    chunked, _ = simulate_slots(topo, sched, "powertcp", 8, lcfg, cfg,
                                record=False, chunk=8)
    c = obs.counters()
    assert c["slots.calls"] == 1 and c["slots.ticks"] == cfg.steps
    assert c["chunk.segments"] > 1
    assert np.array_equal(np.asarray(whole.fct), np.asarray(chunked.fct),
                          equal_nan=True)


_SHARD4_SCRIPT = textwrap.dedent("""
    import contextlib
    import numpy as np
    import jax
    import jax.numpy as jnp
    assert jax.local_device_count() == 4, jax.local_device_count()

    from repro.core import (SimConfig, default_law_config, fat_tree,
                            make_schedule, obs, schedule_as_flows,
                            simulate_slots, simulate_slots_sharded)
    from repro.core import shardslots
    from repro.core.fluid import _host_window
    from repro.launch.mesh import make_mesh

    # a k=4 fat-tree with a 100-flow incast on host 0: hot queue blocks
    # overflow the halo tables on most ticks, not on all
    fab = fat_tree(4)
    topo = fab.topology()
    rng = np.random.default_rng(1)
    src = np.concatenate([rng.integers(0, 16, 40), rng.integers(1, 16, 100)])
    dst = np.concatenate([(src[:40] + rng.integers(1, 16, 40)) % 16,
                          np.zeros(100, int)])
    sizes = np.concatenate([rng.uniform(2e4, 2e5, 40), np.full(100, 3e4)])
    starts = np.concatenate([rng.uniform(0, 4e-4, 40), np.full(100, 1e-4)])
    sched = make_schedule(fab.make_flows(src, dst, sizes, starts, 1e-6,
                                         seed=3))
    cfg = SimConfig(dt=1e-6, steps=600, hist=256)
    lcfg = default_law_config(schedule_as_flows(sched), expected_flows=8.0)
    S, C = 128, 128

    # count the segment programs the chunk loop calls
    real = shardslots._sharded_programs
    calls = []

    def counted(*a, **k):
        init, get_seg = real(*a, **k)

        def get(L):
            f = get_seg(L)

            def g(*args):
                calls.append(L)
                return f(*args)
            return g
        return init, get

    shardslots._sharded_programs = counted
    obs.reset()
    st, _ = simulate_slots_sharded(topo, sched, "powertcp", S, lcfg, cfg,
                                   record=False, devices=4, chunk=C)
    shardslots._sharded_programs = real
    c = obs.counters()
    assert c["chunk.segments"] == len(calls) > 1, (c, calls)
    assert c["slots.ticks"] == sum(calls) == cfg.steps, (c, calls)

    # a plain count: the whole schedule in one window, one tick per call,
    # the overflow flag read after each
    sim = shardslots.SlotSim(topo, sched, shardslots._resolve_law(
        "powertcp", "reference"), lcfg, cfg, S, "reference")
    sched_np = jax.tree_util.tree_map(np.asarray, sched)
    Q, N = topo.num_queues, int(sched_np.start.shape[0])
    mi = shardslots._shard_geometry(sched_np, S, Q, 4)
    init, get_seg = real(sim, mi, make_mesh((4,), ("data",)), None, False)
    win = _host_window(sched_np, 0, N, Q)
    w0 = jnp.asarray(0, jnp.int32)
    carry = init(win, w0)
    plain = 0
    for _ in range(cfg.steps):
        carry, _ = get_seg(1)(carry, win, w0)
        plain += int(carry.ovf)
    assert 0 < plain < cfg.steps, plain
    assert c["halo.fallback_ticks"] == plain, (c, plain)

    # FCTs: bit-identical to a run with every span a null context, and
    # to the single-device reference engine
    span = obs.span
    obs.span = lambda name, **a: contextlib.nullcontext()
    bare, _ = simulate_slots_sharded(topo, sched, "powertcp", S, lcfg, cfg,
                                     record=False, devices=4, chunk=C)
    obs.span = span
    ref, _ = simulate_slots(topo, sched, "powertcp", S, lcfg, cfg,
                            record=False)
    for other in (bare, ref):
        assert np.array_equal(np.asarray(st.fct), np.asarray(other.fct),
                              equal_nan=True)
    print("SHARD4-OBS-OK", c, plain)
""")


def test_sharded_counters_on_4_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep +
                         env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _SHARD4_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SHARD4-OBS-OK" in r.stdout
