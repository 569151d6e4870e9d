"""Multi-pod DiLoCo training with PowerTCP-windowed cross-pod sync.

  PYTHONPATH=src python examples/multipod_diloco.py [--syncs 6] [--inner 5]

The full technique-in-framework story on one (emulated 8-device) machine:
  * two pods train a reduced LM locally for H inner steps each (their data
    shards differ), params diverge;
  * every H steps the DiLoCo outer sync runs as ONE multi-pod SPMD program:
    per-pod deltas -> int8 + error feedback (s8 wire format) -> all-gather
    over the pod axis -> Nesterov outer step on the anchor;
  * in-flight chunk concurrency for that sync is bounded by the
    theta-PowerTCP window controller, fed by bucket timings from the fluid
    DCN backend whose bandwidth follows an RDCN square wave — the window
    adapts between syncs exactly like the paper's Fig. 8 sender.
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P, NamedSharding

from repro.commsched import (ControllerConfig, DCNConfig, make_controller,
                             make_outer_sync, rdcn_bw_fn, run_reduction,
                             window_to_buckets)
from repro.configs import TrainConfig, reduced_config
from repro.launch import make_mesh
from repro.models import init_params, lm_specs, num_bytes
from repro.sharding import tree_shardings
from repro.train import DataConfig, SyntheticData, init_opt, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--syncs", type=int, default=6)
    ap.add_argument("--inner", type=int, default=5)
    a = ap.parse_args()

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = reduced_config("qwen3_14b")
    tcfg = TrainConfig(microbatch=1, remat="none", lr=5e-3, warmup_steps=5,
                       total_steps=200)
    specs = lm_specs(cfg)
    anchor = init_params(specs, jax.random.key(0))
    shardings = tree_shardings(specs, mesh)
    step_fn = jax.jit(make_train_step(cfg, tcfg))
    sync_fn = jax.jit(make_outer_sync(mesh, shardings, compress="int8_ef",
                                      window=2, outer_lr=0.7, momentum=0.9))

    # per-pod state (python-level pods; the SYNC is the real SPMD program)
    pods = []
    for p in range(2):
        pods.append({
            "params": jax.tree.map(jnp.copy, anchor),
            "opt": init_opt(anchor, tcfg),
            "data": SyntheticData(cfg, DataConfig(batch=8, seq=32,
                                                  seed=100 + p)),
        })
    ef = jax.tree.map(lambda x: jnp.zeros((2,) + x.shape, jnp.float32),
                      anchor)
    mom = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), anchor)

    # DCN: 2 GB/s-scale square wave; controller adapts the chunk window
    delta_bytes = float(num_bytes(specs)) / 4.0          # int8 wire
    dcn = DCNConfig(bw_fn=rdcn_bw_fn(day=20e-3, night=5e-3,
                                     hi=50e9, lo=6.25e9), bucket_bytes=2e6)
    ctl = make_controller("theta_powertcp",
                          ControllerConfig(tau=dcn.tau, bw_est=dcn.bw))
    nbuckets = max(int(np.ceil(delta_bytes / dcn.bucket_bytes)), 1)

    print(f"model {cfg.name}: {num_bytes(specs)/1e6:.1f} MB fp32, "
          f"{delta_bytes/1e6:.1f} MB int8 delta, {nbuckets} buckets")
    print(f"{'sync':>4} | {'inner loss p0':>13} | {'inner loss p1':>13} | "
          f"{'window MB':>9} | {'chunks':>6} | {'xfer ms':>8} | "
          f"{'opt ms':>7}")
    step = 0
    for s in range(a.syncs):
        losses = []
        for p, pod in enumerate(pods):
            last = None
            for i in range(a.inner):
                batch = {k: jnp.asarray(v) for k, v in
                         pod["data"].batch_at(step + i).items()}
                pod["params"], pod["opt"], m = step_fn(
                    pod["params"], pod["opt"], batch)
                last = float(m["loss"])
            losses.append(last)
        step += a.inner

        # simulate the DCN transfer under the controller's window; feed the
        # controller the bucket timings it would observe
        r = run_reduction("theta_powertcp", delta_bytes, dcn, record=False)
        w = ctl.window()
        chunks = window_to_buckets(w, dcn.bucket_bytes, nbuckets)
        for _ in range(4):       # a few acks' worth of adaptation per sync
            ctl.on_ack(s * 0.05, r.completion / max(nbuckets, 1) + dcn.tau,
                       dcn.bucket_bytes)

        # the real SPMD outer sync (s8 all-gathers over 'pod', windowed)
        local = jax.tree.map(
            lambda a_, b_: jnp.stack([a_, b_]),
            pods[0]["params"], pods[1]["params"])
        local = jax.tree.map(
            lambda x, sh: jax.device_put(x, NamedSharding(
                mesh, P("pod", *sh.spec))), local, shardings)
        anchor, ef, mom = sync_fn(anchor, local, ef, mom)
        for pod in pods:         # pods restart from the new anchor
            pod["params"] = jax.tree.map(jnp.copy, anchor)
        print(f"{s:4d} | {losses[0]:13.4f} | {losses[1]:13.4f} | "
              f"{w/1e6:9.2f} | {chunks:6d} | {r.completion*1e3:8.2f} | "
              f"{r.optimal*1e3:7.2f}")
    print("\nanchor updated by DiLoCo outer steps; pods re-anchored each "
          "sync. Wire format: s8 all-gathers (see tests/test_commsched).")


if __name__ == "__main__":
    main()
