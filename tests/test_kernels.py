"""Per-kernel shape/dtype sweeps against the ref.py oracles (interpret
mode runs the kernel body in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.powertcp_step import powertcp_step, theta_powertcp_step
from repro.kernels.queue_arrivals import queue_arrivals
from repro.kernels.rmsnorm import rmsnorm

RNG = np.random.default_rng(42)


def _randn(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# -------------------------------------------------------------------------
# flash attention
# -------------------------------------------------------------------------

FLASH_CASES = [
    # B, H, KV, T, S, D, causal, window, dtype
    (2, 4, 2, 128, 128, 64, True, 0, jnp.float32),
    (1, 4, 4, 100, 100, 64, True, 0, jnp.float32),      # ragged T
    (2, 2, 1, 64, 256, 32, True, 0, jnp.float32),       # MQA + T<S offset
    (1, 4, 2, 128, 128, 64, True, 48, jnp.float32),     # sliding window
    (1, 2, 2, 96, 160, 128, False, 0, jnp.float32),     # bidirectional
    (1, 2, 2, 128, 128, 64, True, 0, jnp.bfloat16),
    (1, 1, 1, 8, 8, 256, True, 0, jnp.float32),         # tiny + head_dim 256
    (1, 2, 1, 33, 77, 64, True, 16, jnp.bfloat16),      # ragged everything
]


@pytest.mark.parametrize("B,H,KV,T,S,D,causal,window,dtype", FLASH_CASES)
def test_flash_attention(B, H, KV, T, S, D, causal, window, dtype):
    q = _randn((B, H, T, D), dtype)
    k = _randn((B, KV, S, D), dtype)
    v = _randn((B, KV, S, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          bq=32, bk=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol, rtol=tol)


def test_flash_attention_block_sweep():
    q = _randn((1, 2, 64, 32))
    k = _randn((1, 2, 64, 32))
    v = _randn((1, 2, 64, 32))
    want = ref.flash_attention_ref(q, k, v, causal=True)
    for bq in (8, 16, 64):
        for bk in (8, 32, 64):
            out = flash_attention(q, k, v, causal=True, bq=bq, bk=bk,
                                  interpret=True)
            np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


# -------------------------------------------------------------------------
# rmsnorm
# -------------------------------------------------------------------------

@pytest.mark.parametrize("N,D,dtype", [
    (64, 128, jnp.float32), (100, 256, jnp.bfloat16), (7, 64, jnp.float32),
    (1, 512, jnp.float32), (513, 128, jnp.bfloat16),
])
def test_rmsnorm(N, D, dtype):
    x = _randn((N, D), dtype)
    s = _randn((D,), dtype)
    out = rmsnorm(x, s, interpret=True)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-2, rtol=2e-2)


def test_rmsnorm_3d():
    x = _randn((4, 16, 128), jnp.float32)
    s = _randn((128,))
    np.testing.assert_allclose(rmsnorm(x, s, interpret=True),
                               ref.rmsnorm_ref(x, s), atol=1e-5, rtol=1e-5)


# -------------------------------------------------------------------------
# powertcp_step (Algorithm 1 fused)
# -------------------------------------------------------------------------

def _powertcp_inputs(F, H):
    q = jnp.abs(_randn((F, H))) * 1e6
    qdot = _randn((F, H)) * 1e8
    mu = jnp.abs(_randn((F, H))) * 1e9
    b = jnp.full((F, H), 12.5e9, jnp.float32)
    valid = jnp.asarray(RNG.random((F, H)) > 0.3)
    tau = jnp.full((F,), 20e-6, jnp.float32)
    w = jnp.abs(_randn((F,))) * 1e5 + 1e4
    return dict(q=q, qdot=qdot, mu=mu, b=b, valid=valid, tau=tau, w=w,
                w_old=w * 0.9, gs_prev=jnp.ones((F,), jnp.float32),
                dt_obs=jnp.full((F,), 1e-6, jnp.float32),
                upd=jnp.asarray(RNG.random((F,)) > 0.5),
                beta=jnp.full((F,), 25e3, jnp.float32))


@pytest.mark.parametrize("F,H", [(64, 1), (300, 3), (1000, 2), (17, 4),
                                 (40000, 2)])  # last: several grid steps
def test_powertcp_step(F, H):
    kw = _powertcp_inputs(F, H)
    wk, gk = powertcp_step(**kw, interpret=True)
    wr, gr = ref.powertcp_step_ref(**kw)
    np.testing.assert_allclose(wk, wr, rtol=1e-5)
    np.testing.assert_allclose(gk, gr, rtol=1e-5, atol=1e-6)


def test_powertcp_step_negative_power_matches_law():
    """Negative current (fast drain) must not be floored: kernel == laws.py."""
    from repro.core.laws import norm_power_int, LawConfig
    from repro.core.types import PathObs
    F, H = 32, 2
    kw = _powertcp_inputs(F, H)
    kw["qdot"] = -jnp.abs(kw["qdot"]) * 10     # strongly draining
    wk, gk = powertcp_step(**kw, interpret=True)
    wr, gr = ref.powertcp_step_ref(**kw)
    np.testing.assert_allclose(wk, wr, rtol=1e-5)


# -------------------------------------------------------------------------
# theta_powertcp_step (Algorithm 2 fused)
# -------------------------------------------------------------------------

def _theta_inputs(F):
    tau = jnp.full((F,), 20e-6, jnp.float32)
    theta = tau * (1.0 + jnp.abs(_randn((F,))) * 0.5)
    prev = tau * (1.0 + jnp.abs(_randn((F,))) * 0.5)
    w = jnp.abs(_randn((F,))) * 1e5 + 1e4
    return dict(theta=theta, prev_theta=prev, tau=tau, w=w, w_old=w * 0.9,
                gs_prev=jnp.ones((F,), jnp.float32),
                dt_obs=jnp.full((F,), 1e-6, jnp.float32),
                upd=jnp.asarray(RNG.random((F,)) > 0.5),
                beta=jnp.full((F,), 25e3, jnp.float32))


@pytest.mark.parametrize("F", [16, 256, 1000])
def test_theta_powertcp_step(F):
    kw = _theta_inputs(F)
    wk, gk, pk = theta_powertcp_step(**kw, interpret=True)
    wr, gr, pr = ref.theta_powertcp_step_ref(**kw)
    np.testing.assert_allclose(wk, wr, rtol=1e-5)
    np.testing.assert_allclose(gk, gr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(pk, pr, rtol=1e-6)


def test_theta_powertcp_step_matches_law():
    """Kernel == laws.theta_powertcp_update on identical state/obs."""
    from repro.core.laws import (LawConfig, ThetaPowerTCPState,
                                 theta_powertcp_update)
    from repro.core.types import PathObs
    F = 64
    kw = _theta_inputs(F)
    wk, gk, pk = theta_powertcp_step(**kw, interpret=True)
    cfg = LawConfig(gamma=0.9, beta=kw["beta"], tau=kw["tau"])
    obs = PathObs(q=None, qdot=None, mu=None, b=None, valid=None,
                  theta=kw["theta"], w_old=kw["w_old"], dt_obs=kw["dt_obs"],
                  ecn_frac=None)
    st = ThetaPowerTCPState(kw["gs_prev"], kw["prev_theta"])
    st2, wl, _ = theta_powertcp_update(st, obs, kw["w"], None, kw["upd"],
                                       cfg, 0.0)
    np.testing.assert_allclose(wk, wl, rtol=1e-5)
    np.testing.assert_allclose(gk, st2.gamma_smooth, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(pk, st2.prev_theta, rtol=1e-6)


# -------------------------------------------------------------------------
# queue_arrivals (scatter-free fluid queue update)
# -------------------------------------------------------------------------

@pytest.mark.parametrize("H,F,Q", [(1, 32, 16), (3, 128, 100), (2, 50, 7),
                                   (4, 256, 300),
                                   (3, 900, 700)])  # several blocks each way
def test_queue_arrivals(H, F, Q):
    lam = jnp.abs(_randn((H, F)))
    path = RNG.integers(0, Q, (H, F))
    onehot = jnp.asarray(np.eye(Q)[path], jnp.float32)
    q0 = jnp.abs(_randn((Q,)))
    outr = jnp.abs(_randn((Q,)))
    caps = jnp.full((Q,), 5.0, jnp.float32)
    a1, q1 = queue_arrivals(lam, onehot, q0, outr, caps, dt=0.5,
                            interpret=True)
    a2, q2 = ref.queue_arrivals_ref(lam, onehot, q0, outr, caps, 0.5)
    np.testing.assert_allclose(a1, a2, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(q1, q2, atol=1e-4, rtol=1e-5)


def test_queue_arrivals_matches_simulator_scatter():
    """The dense incidence form must equal the simulator's scatter-add."""
    H, F, Q = 2, 40, 12
    lam = jnp.abs(_randn((H, F)))
    path = RNG.integers(0, Q, (H, F))
    onehot = jnp.asarray(np.eye(Q)[path], jnp.float32)
    arr_kernel, _ = queue_arrivals(lam, onehot, jnp.zeros(Q), jnp.zeros(Q),
                                   jnp.full((Q,), 1e9), dt=1.0,
                                   interpret=True)
    arr_scatter = jnp.zeros(Q)
    for h in range(H):
        arr_scatter = arr_scatter.at[path[h]].add(lam[h])
    np.testing.assert_allclose(arr_kernel, arr_scatter, rtol=1e-5, atol=1e-5)
