"""Fluid-queue update kernels: dense (MXU matmul) and sparse (CSR) forms.

The simulator's inner loop scatters delayed per-hop flow rates into queue
arrival sums (``zeros.at[path].add(lam)``). Two accelerated forms exist:

Dense (``queue_arrivals``, the ``"fused"`` backend): scatters serialize
badly on TPU; the TPU-native adaptation (DESIGN.md section 2) is a dense
incidence form: the hops fold into the contraction axis, so the arrivals
are ONE ``[1, H*F] x [H*F, Q]`` matmul on the MXU, followed by the fused
elementwise queue integration ``q' = clip(q + (arr - out) dt, 0, caps)``.
The grid tiles the queue axis (parallel) and the contraction axis
(accumulated in the resident output block), with 128-multiple blocks
sized from a VMEM budget, so the 256-host leaf-spine (288 queues) and
the k=16 fat-tree (5,120 queues) both compile for TPU at any flow count.
The matmul runs at full f32 precision but REASSOCIATES each queue's sum,
so the dense form is numerically close to (not bitwise equal with) the
reference scatter.

Sparse (``queue_arrivals_sparse``, the ``"megakernel"`` backend,
DESIGN.md section 13): the incidence of a slot pool is tiny
(nnz <= S*hops, vs the S*Q dense form) and changes only on admission, so
the megakernel keeps the CSR view — the flat per-slot hop list
``path.reshape(-1)`` with values ``lam_del.reshape(-1)`` — and
accumulates with a segment-sum in slot-major order. Per-tick cost is
O(nnz), and the accumulation order is IDENTICAL to the reference
engine's masked scatter-add, which is what lets the megakernel backend
bit-match the reference backend (the dense matmul cannot).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl


def _pin(x):
    """Identity optimization barrier (see ``core.laws._pin``; duplicated
    here so kernels stay importable without the core package)."""
    return jax.lax.optimization_barrier(x)


def _nofma(x):
    """FMA-contraction blocker (see ``core.laws._nofma``; duplicated for
    the same importability reason)."""
    return jnp.maximum(x, jnp.float32(-3e38))


def ordered_scatter_add(zero: jnp.ndarray, idx: jnp.ndarray,
                        vals: jnp.ndarray, unroll_max: int = 128):
    """``zero.at[idx].add(vals)`` with a bit-identical unrolled lowering
    for small row counts.

    XLA CPU lowers a float scatter-add to a per-row ``while`` loop whose
    per-iteration overhead (condition + tuple shuffling) costs ~1us —
    for a [16]-row scatter into a 2-queue VOQ that while loop IS half the
    simulator tick. With ``rows <= unroll_max`` this emits straight-line
    fused elementwise code instead: one masked add per row, applied in
    ascending flat row order — exactly the scatter's update order — and
    the +0.0 the mask contributes elsewhere is an additive identity (the
    accumulator and all arrival contributions are non-negative, so no
    -0.0 exists anywhere). The result is therefore bit-for-bit the
    scatter's, on every engine and batch width; larger row counts fall
    through to the native scatter.
    """
    idx = idx.reshape(-1)
    vals = vals.reshape(-1)
    rows = int(idx.shape[0])
    if rows > unroll_max:
        return zero.at[idx].add(vals)
    qidx = jnp.arange(zero.shape[0], dtype=idx.dtype)
    acc = zero
    for i in range(rows):
        acc = acc + jnp.where(qidx == idx[i], vals[i], 0.0)
    return acc


def suggest_maxdeg(path, num_queues: int, slots: int, cap: int = 64,
                   default: int = 32) -> int:
    """Static CSR width for ``build_csr_gather`` from a compiled path set.

    The true per-tick degree of a queue is bounded by BOTH the pool size
    (at most ``slots`` flows are concurrently resident) and the static
    degree of the whole schedule's hop table (a queue no flow in the
    schedule ever traverses twice cannot exceed its static count — on a
    routed fabric the victim downlink of an incast burst has degree
    exactly fan-in + 1, and a lightly-shared fat-tree core queue far
    less than S). Sizing the CSR to that bound keeps the unrolled
    column adds short AND avoids the per-tick scatter fallback the old
    fixed width forced whenever a hot queue's degree crossed it.

    Degrees beyond ``cap`` would unroll into more straight-line adds
    than they save, so those fabrics keep the historical ``default``
    width and rely on the (bit-identical) runtime overflow fallback.
    """
    flat = np.asarray(path).reshape(-1)
    flat = flat[(flat >= 0) & (flat < num_queues)]
    d = int(np.bincount(flat, minlength=1).max()) if flat.size else 1
    d = max(d, 1)
    if d > cap:
        d = default
    return max(1, min(d, int(slots)))


def stable_sort_ids(ids: jnp.ndarray, bound: int):
    """Stable ascending sort of int ids in ``[0, bound]``: returns
    ``(sorted_ids, order)`` with ``order`` the stable argsort.

    When ``(bound + 2) * n`` fits int32 the stable argsort is replaced by
    a plain sort of the packed keys ``id * n + index`` — the flat index
    is the tiebreaker, so ``key % n`` IS the stable order and
    ``key // n`` the sorted ids, at a fraction of the stable argsort's
    cost on XLA CPU. Both paths return identical bits."""
    n = int(ids.shape[0])
    if (bound + 2) * n < 2**31:
        key = jax.lax.sort(ids.astype(jnp.int32) * n
                           + jnp.arange(n, dtype=jnp.int32))
        return key // n, key % n
    order = jnp.argsort(ids, stable=True)
    return ids[order], order


def seg_ranks(sorted_ids: jnp.ndarray) -> jnp.ndarray:
    """Per-element rank within its run of equal ids (ids ascending):
    a running max of the change points — equivalent to
    ``arange - searchsorted(ids, ids, "left")``, cheaper on CPU."""
    n = int(sorted_ids.shape[0])
    idx = jnp.arange(n, dtype=jnp.int32)
    change = jnp.concatenate([jnp.ones((1,), bool),
                              sorted_ids[1:] != sorted_ids[:-1]])
    return idx - jax.lax.cummax(jnp.where(change, idx, 0))


def build_csr_gather(path: jnp.ndarray, num_queues: int, maxdeg: int):
    """Invert the pool's hop list into a per-queue gather table.

    ``path`` is the [S, H] hop table; the result ``inv`` is
    [Q+1, maxdeg] int32 where ``inv[q, j]`` is the flat (slot-major)
    index of queue q's j-th contributor in ascending flat order — i.e. a
    CSR of the incidence, padded with the sentinel index S*H (which the
    consumer maps to a 0.0 contribution). ``overflow`` is True when some
    real queue has more than ``maxdeg`` contributors, in which case the
    consumer must fall back to the scatter form (the table is truncated).
    Sentinel (invalid) hops are excluded — their contributions are
    structurally zero and the sentinel queue's arrival sum is exactly
    +0.0 either way.

    Cost is one sort + one scatter over S*H elements; the slot
    engine's hop table changes only on admission, so the megakernel
    rebuilds this inside the (gated) admit pass — O(nnz log nnz)
    amortized over the many ticks between arrivals — and pays one
    [Q+1, maxdeg] gather + maxdeg in-order column adds per tick instead
    of an S*H-row scatter.

    When ``(num_queues + 2) * nnz`` fits int32 the stable argsort is
    replaced by a plain sort of the packed keys ``q * nnz + flat_index``
    — the flat index is the tiebreaker, so ``key % nnz`` IS the stable
    order and ``key // nnz`` the sorted queue ids, at a fraction of the
    stable argsort's cost (XLA CPU's stable argsort of the [nnz] id
    array is several times slower than one plain int sort). The packed
    path produces the identical ``inv`` table bit-for-bit.
    """
    flat_q = path.reshape(-1)
    nnz = int(flat_q.shape[0])
    sorted_q, order = stable_sort_ids(flat_q, num_queues)
    # rank of each contribution within its queue (ascending flat index,
    # because the sort is stable)
    rank_sorted = seg_ranks(sorted_q)
    real = sorted_q < num_queues
    overflow = jnp.any(real & (rank_sorted >= maxdeg))
    cell = jnp.where(real & (rank_sorted < maxdeg),
                     sorted_q * maxdeg + jnp.minimum(rank_sorted,
                                                     maxdeg - 1),
                     (num_queues + 1) * maxdeg)
    inv = jnp.full(((num_queues + 1) * maxdeg + 1,), nnz,
                   jnp.int32).at[cell].set(order.astype(jnp.int32),
                                           mode="drop")
    return inv[:-1].reshape(num_queues + 1, maxdeg), overflow


def build_csr_gather_padded(path: jnp.ndarray, num_queues: int,
                            maxdeg: int, rows: int):
    """``build_csr_gather`` padded to ``rows`` queue rows.

    The sharded single-scenario engine (core/shardslots.py) partitions
    the inverted incidence row-wise over the device mesh; ``rows`` is the
    queue count rounded up to a multiple of the shard count so every
    shard owns an equal block. Pad rows hold only the sentinel index
    (``S*H``), which ``csr_gather_arrivals`` maps to +0.0 — a shard that
    owns pad rows accumulates exact zeros for them. ``overflow`` keeps
    its whole-table meaning. ``csr_gather_arrivals`` works unchanged on
    a row block: each queue's in-order column-add chain lives entirely
    within the row that owns it.
    """
    inv, overflow = build_csr_gather(path, num_queues, maxdeg)
    extra = rows - (num_queues + 1)
    if extra > 0:
        nnz = int(path.reshape(-1).shape[0])
        inv = jnp.concatenate(
            [inv, jnp.full((extra, maxdeg), nnz, jnp.int32)])
    return inv, overflow


def csr_gather_arrivals(contrib: jnp.ndarray, inv: jnp.ndarray,
                        zero: jnp.ndarray) -> jnp.ndarray:
    """Arrival sums from the inverted incidence: one [Q+1, maxdeg] gather
    plus maxdeg in-order column adds. Column j holds every queue's j-th
    contributor (ascending flat order), so each queue's accumulation
    chain is exactly the scatter's — bit-for-bit — and the sentinel
    pad contributes +0.0 (an additive identity on the non-negative
    arrivals)."""
    padded = jnp.concatenate([contrib.reshape(-1),
                              jnp.zeros((1,), contrib.dtype)])
    m = padded[inv]                                   # [Q+1, maxdeg]
    arr = zero
    for j in range(inv.shape[1]):                     # in-order, unrolled
        arr = arr + m[:, j]
    return arr


def apply_loss(arr: jnp.ndarray, keep: jnp.ndarray) -> jnp.ndarray:
    """Fold per-link loss into the ACCUMULATED queue arrivals.

    Loss is applied post-scatter — every engine scales the identical
    accumulated sum, so the scaled arrivals (and the out/q integration
    they feed) stay bit-identical across engines; scaling per-hop
    contributions pre-scatter would round each engine's accumulation
    chain apart. Pinned + contraction-blocked like the integration
    itself; ``keep == 1.0`` rows are exact (x * 1.0 == x in f32), which
    is the zero-impairment bitwise contract (core/impair.py)."""
    return _nofma(_pin(arr * keep))


def integrate_arrivals(arr: jnp.ndarray, q: jnp.ndarray, bw: jnp.ndarray,
                       caps: jnp.ndarray, *, dt: float):
    """The fluid-queue integration step shared by every sparse queue
    form: mirrors ``fluid._queue_update`` exactly, pins and contraction
    blockers included (the barrier stops XLA rewrites, the ``_nofma``
    stops LLVM from contracting the integration into an FMA — either
    would break cross-engine bit-equality). Returns (out, q_new)."""
    q_new = jnp.clip(q + _nofma(_pin((arr - bw) * dt)), 0.0, caps)
    out = jnp.where(q > 0.0, bw, jnp.minimum(arr, bw))
    return out, q_new.at[-1].set(0.0)


def queue_arrivals_sparse(lam_del: jnp.ndarray, path: jnp.ndarray,
                          valid: jnp.ndarray, q: jnp.ndarray,
                          bw: jnp.ndarray, caps: jnp.ndarray, *, dt: float,
                          unroll_max: int = 128):
    """Sparse (CSR / flat hop-list) queue update, self-contained form.

    ``lam_del``/``path``/``valid`` are the pool's [S, H] delayed rates and
    hop table; the incidence is kept in its sparse form — the flattened
    per-slot hop list — and accumulated with a slot-major segment sum
    (``ordered_scatter_add``), so per-tick cost is O(nnz) and the
    accumulation order is identical to the reference engine's masked
    scatter-add (bit-for-bit, unlike the dense matmul of
    ``queue_arrivals``). Returns (arrivals, out, q_new).

    The megakernel (core/megakernel.py) composes the same pieces —
    ``ordered_scatter_add``/``csr_gather_arrivals`` for the arrivals plus
    ``integrate_arrivals`` — inline, because it interleaves the packed
    telemetry-row write and the inverted-incidence cond between them;
    this function is the standalone one-call form of that pipeline
    (asserted bit-identical to ``fluid._queue_update`` in
    tests/test_megakernel.py).
    """
    contrib = jnp.where(valid, lam_del, 0.0)
    arr = ordered_scatter_add(jnp.zeros_like(q), path, contrib,
                              unroll_max=unroll_max)
    out, q_new = integrate_arrivals(arr, q, bw, caps, dt=dt)
    return arr, out, q_new


def update_incidence(incidence: jnp.ndarray, path: jnp.ndarray,
                     changed: jnp.ndarray, num_queues: int) -> jnp.ndarray:
    """Dynamic-update of a slot-sized incidence on admit/retire.

    ``incidence`` is the [H, S, Q+1] one-hot path incidence carried by the
    flow-slot streaming engine's scan state; ``path`` [S, H] is the pool's
    current hop table and ``changed`` [S] marks slots whose occupancy
    changed this tick (admissions — retired slots keep their stale path,
    which is exact because a retiring flow's delayed rates are zero by
    construction, see fluid.slot_step). Unchanged columns pass through
    untouched, so the update is a masked select rather than a rebuild of
    the scatter graph; the fresh one-hot columns cost O(H*S*Q), the same
    order as the incidence matmul itself consumes every tick.

    Invalid (sentinel) hops become all-zero rows, exactly as in
    ``fluid.build_incidence``.
    """
    valid = path < num_queues
    oh = jax.nn.one_hot(path, num_queues + 1, dtype=jnp.float32)
    cols = jnp.swapaxes(oh * valid[..., None].astype(jnp.float32), 0, 1)
    return jnp.where(changed[None, :, None], cols, incidence)


LANES = 128
ONEHOT_BLOCK_ELEMS = 1 << 19      # 2 MiB f32 incidence block per grid step
MAX_QUEUE_BLOCK = 512


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def incidence_tiling(K: int, Q: int):
    """(bk, Kp, bq, Qp): contraction and queue block sizes of the dense
    kernel (128-multiples) and the padded extents they divide."""
    bq = min(_round_up(Q, LANES), MAX_QUEUE_BLOCK)
    bk = min(_round_up(K, LANES),
             max(LANES, ONEHOT_BLOCK_ELEMS // bq // LANES * LANES))
    return bk, _round_up(K, bk), bq, _round_up(Q, bq)


def _kernel(lam_ref, onehot_ref, q_ref, out_ref, caps_ref, arr_ref,
            qnew_ref, *, dt):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        arr_ref[...] = jnp.zeros_like(arr_ref)

    arr_ref[...] += jax.lax.dot(lam_ref[...], onehot_ref[...],
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _integrate():
        qnew_ref[...] = jnp.clip(
            q_ref[...] + (arr_ref[...] - out_ref[...]) * dt,
            0.0, caps_ref[...])


@functools.partial(jax.jit, static_argnames=("dt", "interpret"))
def queue_arrivals(lam_del, onehot, q, out_rate, caps, *, dt,
                   interpret=None):
    """lam_del: [H,F]; onehot: [H,F,Q]; q/out_rate/caps: [Q] ->
    (arrivals [Q], q_new [Q])."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    H, F, Q = onehot.shape
    K = H * F
    bk, Kp, bq, Qp = incidence_tiling(K, Q)
    lam = jnp.pad(lam_del.astype(jnp.float32).reshape(1, K),
                  ((0, 0), (0, Kp - K)))
    inc = jnp.pad(onehot.astype(jnp.float32).reshape(K, Q),
                  ((0, Kp - K), (0, Qp - Q)))
    row = lambda x: jnp.pad(x.astype(jnp.float32), (0, Qp - Q)).reshape(1, Qp)
    qspec = pl.BlockSpec((1, bq), lambda j, k: (0, j))
    arr, qnew = pl.pallas_call(
        functools.partial(_kernel, dt=dt),
        grid=(Qp // bq, Kp // bk),
        in_specs=[
            pl.BlockSpec((1, bk), lambda j, k: (0, k)),
            pl.BlockSpec((bk, bq), lambda j, k: (k, j)),
            qspec, qspec, qspec,
        ],
        out_specs=(qspec, qspec),
        out_shape=(jax.ShapeDtypeStruct((1, Qp), jnp.float32),
                   jax.ShapeDtypeStruct((1, Qp), jnp.float32)),
        interpret=interpret,
    )(lam, inc, row(q), row(out_rate), row(caps))
    return arr[0, :Q], qnew[0, :Q]
