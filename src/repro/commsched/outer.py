"""Cross-pod parameter synchronization: chunked, compressed, window-bounded.

Two DCN strategies (TrainConfig.multipod_strategy):
  sync    every step: XLA's automatic cross-pod gradient all-reduce (batch
          sharded over the pod axis). Simple, bandwidth-hungry.
  diloco  H local steps per pod, then this module's outer sync: each pod
          computes delta = anchor - theta_pod; per-leaf buckets are
          int8-quantized with pod-local error feedback, all-gathered over
          the pod axis (wire format stays int8 — 4x fewer DCN bytes than
          fp32), de-quantized, averaged, and applied through Nesterov
          momentum (DiLoCo).

``make_outer_sync`` lowers as one SPMD program on the multi-pod mesh via
``shard_map`` over 'pod'; leaves keep their FSDP/TP layout on data/model
(the caller passes the parameter PartitionSpec tree), so the all-gather
moves shard-sized int8 blocks only. In-flight concurrency is bounded to
``window`` buckets by ``optimization_barrier`` chaining — the XLA-level
realization of the PowerTCP window whose value the host control loop adapts
between steps (repro.commsched.controller, validated in simbackend).
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


# -------------------------------------------------------------------------
# Bucketizer: group pytree leaves into ~equal-byte buckets (for grads-level
# scheduling and the simulator bridge; outer_sync buckets = stacked leaves)
# -------------------------------------------------------------------------


def bucketize(tree, target_bytes: float = 64e6) -> List[List[Tuple]]:
    """Greedy first-fit over leaves in deterministic key order, so every
    pod builds identical buckets. Returns lists of (keypath, leaf)."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    buckets, cur, cur_bytes = [], [], 0.0
    for path, leaf in leaves:
        b = leaf.size * leaf.dtype.itemsize
        if cur and cur_bytes + b > target_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0.0
        cur.append((path, leaf))
        cur_bytes += b
    if cur:
        buckets.append(cur)
    return buckets


def window_to_buckets(window_bytes: float, bucket_bytes: float,
                      nbuckets: int) -> int:
    """Bridge: controller window (bytes) -> in-flight bucket bound."""
    return int(max(1, min(round(window_bytes / max(bucket_bytes, 1.0)),
                          nbuckets)))


# -------------------------------------------------------------------------
# int8 + error feedback (standalone helpers; outer_sync inlines the same
# math inside its shard_map body so the wire format stays s8)
# -------------------------------------------------------------------------


def quantize_int8(x, ef):
    """Per-tensor symmetric int8 with error feedback.
    Returns (q int8, scale, new_ef)."""
    y = x.astype(jnp.float32) + ef
    scale = jnp.maximum(jnp.max(jnp.abs(y)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(y / scale), -127, 127).astype(jnp.int8)
    return q, scale, y - q.astype(jnp.float32) * scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


# -------------------------------------------------------------------------
# Outer sync (DiLoCo + int8/EF + windowed chunking)
# -------------------------------------------------------------------------


def make_outer_sync(mesh: Mesh, shardings, compress: str = "int8_ef",
                    window: int = 2, outer_lr: float = 0.7,
                    momentum: float = 0.9):
    """Builds outer_sync(anchor, local_params, ef, mom) ->
    (new_anchor, new_ef, new_mom).

    anchor/mom: replicated across pods. local_params/ef: per-pod values
    with a leading pod dim of size npods, leaf spec P('pod', *anchor_spec).
    ``shardings`` is the anchor tree of NamedShardings (from
    sharding.tree_shardings) — data/model FSDP/TP layout is preserved so
    the pod all-gather moves shard-sized blocks only.
    """
    npods = dict(zip(mesh.axis_names, mesh.devices.shape)).get("pod", 1)

    def pod_mean_factory(spec: P):
        pod_spec = P("pod", *spec)

        def inner(d_blk, e_blk):
            """d_blk/e_blk: local [1, ...] blocks on this pod's shard."""
            if compress == "int8_ef":
                y = d_blk + e_blk
                scale = jnp.maximum(jnp.max(jnp.abs(y)), 1e-12) / 127.0
                q = jnp.clip(jnp.round(y / scale), -127, 127).astype(jnp.int8)
                deq = q.astype(jnp.float32) * scale
                new_e = y - deq                         # pod-local EF
                qg = jax.lax.all_gather(q, "pod", axis=0, tiled=True)
                sg = jax.lax.all_gather(scale, "pod", axis=0)
                deqg = qg.astype(jnp.float32) * sg.reshape(
                    (npods,) + (1,) * (qg.ndim - 1))
                mean = jnp.mean(deqg, axis=0, keepdims=True)
                return mean, new_e
            xg = jax.lax.all_gather(d_blk, "pod", axis=0, tiled=True)
            return jnp.mean(xg, axis=0, keepdims=True), e_blk

        return shard_map(inner, mesh=mesh,
                         in_specs=(pod_spec, pod_spec),
                         out_specs=(pod_spec, pod_spec),
                         check_vma=False)

    def outer_sync(anchor, local_params, ef, mom):
        deltas = jax.tree.map(
            lambda a, lp: a.astype(jnp.float32)[None]
            - lp.astype(jnp.float32), anchor, local_params)

        d_leaves, treedef = jax.tree.flatten(deltas)
        e_leaves = jax.tree.leaves(ef)
        s_leaves = [s.spec for s in jax.tree.leaves(shardings)]
        means, new_efs = [], []
        for i, (d, e, s) in enumerate(zip(d_leaves, e_leaves, s_leaves)):
            if window > 0 and i >= window:
                # bound concurrency: this bucket's collective cannot start
                # until bucket (i - window) finished — dependency on its
                # result, injected before the collective's input.
                prev = means[i - window]
                d, _ = jax.lax.optimization_barrier((d, prev))
            m, ne = pod_mean_factory(s)(d, e)
            means.append(m)
            new_efs.append(ne)

        mean_tree = jax.tree.unflatten(treedef, [m[0] for m in means])
        new_ef = jax.tree.unflatten(treedef, new_efs)
        # Nesterov outer step on the averaged delta (anchor - mean(theta_p))
        new_mom = jax.tree.map(
            lambda v, g: momentum * v.astype(jnp.float32) + g,
            mom, mean_tree)
        new_anchor = jax.tree.map(
            lambda a, v, g: (a.astype(jnp.float32)
                             - outer_lr * (momentum * v + g)).astype(a.dtype),
            anchor, new_mom, mean_tree)
        return new_anchor, new_ef, new_mom

    return outer_sync
