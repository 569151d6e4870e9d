"""Transformer building blocks (pure JAX, spec-declared params).

Everything is a (specs, apply) pair. Apply functions are jit-friendly,
mesh-agnostic (sharding arrives via ``constrain`` which no-ops outside a
``use_rules`` context) and support three execution modes:

  forward  — full-sequence training / prefill
  decode   — single-token step against a KV cache (full or ring-buffer)

Numerics follow the usual mixed-precision recipe: params in
``cfg.param_dtype``, math in ``cfg.compute_dtype``, softmax/norms in fp32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from ..sharding.axes import active_mesh, constrain
from .spec import ParamSpec, fan_in_normal

from jax.sharding import PartitionSpec as P


# --------------------------------------------------------------------------
# TP contraction with explicit mixed-precision reduction (§Perf iteration 3)
#
# XLA partitions a dot whose contraction dim is model-sharded into
# local-dot + all-reduce of the f32 accumulator: wire = 2(g-1)/g x 4B x n
# (measured 268 MB f32 per layer on llama3-405b). This helper decomposes
# the reduction OUR way inside a partial-manual shard_map over 'model':
#
#   local dot -> f32 reduce-scatter (exact accumulation)
#             -> bf16 all-gather    (half the redistribution bytes)
#
# wire = (g-1)/g x (4B + 2B) x n  — 25% less than XLA's f32 all-reduce.
# On real TPU the reduce-scatter itself runs in bf16 (wire 2B+2B = 50% cut);
# this container's XLA-CPU AllReducePromotion pass crashes on any bf16
# reduction collective (CloneAllReduce bug), so the f32-RS variant is what
# the dry-run measures. Falls back to a plain einsum when no mesh is
# active, dims don't divide, or cfg.tp_reduce == "xla".
# --------------------------------------------------------------------------


@jax.custom_vjp
def _ag_bf16_model(ys):
    """bf16 all-gather over 'model' (axis 2) with an f32-reduced backward.

    The natural transpose of a bf16 all-gather is a bf16 reduce-scatter —
    which XLA-CPU's AllReducePromotion pass crashes on (and on TPU would be
    the desired native op). The custom backward reduce-scatters the
    cotangent in f32 and hands back bf16.
    """
    return jax.lax.all_gather(ys, "model", axis=2, tiled=True)


def _ag_fwd(ys):
    return _ag_bf16_model(ys), None


def _ag_bwd(_, ct):
    cts = jax.lax.psum_scatter(ct.astype(jnp.float32), "model",
                               scatter_dimension=2, tiled=True)
    return (cts.astype(jnp.bfloat16),)


_ag_bf16_model.defvjp(_ag_fwd, _ag_bwd)


def tp_proj_out(h, w, cfg):
    """h: [B, T, F] (F model-sharded, B batch-sharded), w: [F, d] ->
    [B, T, d] batch-sharded, replicated over model; reduction over F across
    model shards in explicit mixed precision.

    All mesh axes are MANUAL here: a partial-manual spec that mentions only
    'model' binds the batch dim replicated over data — measured 11x wire
    regression on llama3-405b before this was made fully manual (§Perf
    iteration 3 log, refuted-then-fixed)."""
    cd = cfg.compute_dtype
    mesh = active_mesh()
    f = h.shape[-1]
    d = w.shape[-1]
    if cfg.tp_reduce != "bf16" or mesh is None:
        return jnp.einsum("btf,fd->btd", h.astype(cd), w.astype(cd))
    sizes = dict(mesh.shape)
    g = sizes.get("model", 1)
    bdims = tuple(a for a in ("pod", "data") if a in sizes)
    dp = 1
    for a in bdims:
        dp *= sizes[a]
    if (g == 1 or f % g != 0 or d % g != 0 or not bdims
            or h.shape[0] % dp != 0):
        return jnp.einsum("btf,fd->btd", h.astype(cd), w.astype(cd))

    def mm(h_blk, w_blk):
        y = jnp.einsum("btf,fd->btd", h_blk.astype(cd), w_blk.astype(cd))
        ys = jax.lax.psum_scatter(y.astype(jnp.float32), "model",
                                  scatter_dimension=2, tiled=True)
        return _ag_bf16_model(ys.astype(jnp.bfloat16))

    bspec = bdims if len(bdims) > 1 else bdims[0]
    out = shard_map(
        mm, mesh=mesh,
        in_specs=(P(bspec, None, "model"), P("model", None)),
        out_specs=P(bspec, None, None),
        axis_names=set(mesh.axis_names), check_vma=False)(h, w)
    return out.astype(cd)

# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------


def norm_specs(d: int, kind: str, dtype: str):
    if kind == "layernorm":
        return {"scale": ParamSpec((d,), dtype, ("embed",), "ones"),
                "bias": ParamSpec((d,), dtype, ("embed",), "zeros")}
    return {"scale": ParamSpec((d,), dtype, ("embed",), "ones")}


def norm_apply(p, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE (supports partial rotation — stablelm rotates 25 % of head_dim)
# --------------------------------------------------------------------------


def rope(x, positions, frac: float = 1.0, theta: float = 10000.0):
    """x: [..., T, H?, D]; positions: broadcastable to [..., T]."""
    d = x.shape[-1]
    rot = int(d * frac) // 2 * 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs       # [...,T,half]
    ang = jnp.expand_dims(ang, axis=-2)                          # head axis
    x1, x2 = xr[..., :half], xr[..., half:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    y = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([y.astype(x.dtype), xp], axis=-1)


# --------------------------------------------------------------------------
# Attention (GQA; causal / sliding-window / bidirectional / cross)
# --------------------------------------------------------------------------


def attn_specs(cfg, cross: bool = False):
    d, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.param_dtype
    s = {
        "wq": fan_in_normal((d, H, Dh), 0, dt, ("embed", "heads", "head_dim")),
        "wk": fan_in_normal((d, KV, Dh), 0, dt, ("embed", "kv", "head_dim")),
        "wv": fan_in_normal((d, KV, Dh), 0, dt, ("embed", "kv", "head_dim")),
        "wo": fan_in_normal((H * Dh, d), 0, dt, (None, "embed")),
    }
    if cfg.qk_norm and not cross:
        s["q_norm"] = ParamSpec((Dh,), dt, (None,), "ones")
        s["k_norm"] = ParamSpec((Dh,), dt, (None,), "ones")
    return s


def _qkv(p, xq, xkv, cfg, q_positions, k_positions, use_rope=True):
    cd = cfg.compute_dtype
    q = jnp.einsum("btd,dhk->bthk", xq.astype(cd), p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", xkv.astype(cd), p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", xkv.astype(cd), p["wv"].astype(cd))
    if "q_norm" in p:
        q = _rms(q, p["q_norm"])
        k = _rms(k, p["k_norm"])
    if use_rope:
        q = rope(q, q_positions, cfg.rope_frac, cfg.rope_theta)
        k = rope(k, k_positions, cfg.rope_frac, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, cfg):
    """q [B,T,H,Dh], k/v [B,S,KV,Dh], mask broadcastable to [B,?,T,S]."""
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, T, KV, G, Dh) * float(1.0 / np.sqrt(Dh))
    scores = jnp.einsum("btkgh,bskh->bkgts", q, k).astype(jnp.float32)
    if mask.ndim == 2:          # [T,S]
        mask = mask[None, None, None]
    elif mask.ndim == 3:        # [B,T,S]
        mask = mask[:, None, None]
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", w, v)
    return out.reshape(B, T, H * Dh)


def _self_mask(kind: str, T: int, window: int, q0: int = 0):
    qi = q0 + jnp.arange(T)[:, None]
    kj = jnp.arange(q0 + T)[None, :]
    if kind == "bidir":
        return jnp.ones((T, q0 + T), bool)
    m = kj <= qi
    if kind == "local" and window > 0:
        m &= kj > qi - window
    return m


def attn_forward(p, x, cfg, kind: str = "causal", pos0: int = 0,
                 return_kv: bool = False):
    """Full-sequence self-attention (training / prefill)."""
    B, T, _ = x.shape
    pos = pos0 + jnp.arange(T)[None, :]
    q, k, v = _qkv(p, x, x, cfg, pos, pos, use_rope=not cfg.learned_pos)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv", None)
    mask = _self_mask(kind, T, cfg.window)
    out = _sdpa(q, k, v, mask, cfg)
    y = tp_proj_out(out, p["wo"], cfg)
    y = constrain(y, "batch", None, None)
    if return_kv:
        return y, (k, v)
    return y


def cross_attn_forward(p, x, enc_kv, cfg):
    """Decoder cross-attention; enc_kv = (k, v) precomputed from encoder."""
    cd = cfg.compute_dtype
    q = jnp.einsum("btd,dhk->bthk", x.astype(cd), p["wq"].astype(cd))
    k, v = enc_kv
    mask = jnp.ones((x.shape[1], k.shape[1]), bool)
    out = _sdpa(q, k, v, mask, cfg)
    return jnp.einsum("bte,ed->btd", out, p["wo"].astype(cd))


def cross_kv(p, enc_out, cfg):
    cd = cfg.compute_dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out.astype(cd), p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", enc_out.astype(cd), p["wv"].astype(cd))
    return k, v


def attn_decode(p, x, cache_k, cache_v, index, cfg, kind: str = "causal"):
    """One-token decode. cache_[kv]: [B, S, KV, Dh] (S = max or ring size).

    ``index`` — number of tokens already in context (position of this token).
    Full cache (kind=causal/bidir-cross n/a): write at ``index``.
    Ring cache (kind=local): write at ``index % S``; validity reconstructed
    from ``index`` (slot s holds position index - ((index - s) mod S)).
    """
    B, S, KV, Dh = cache_k.shape
    pos = jnp.full((B, 1), index, jnp.int32)
    q, k_new, v_new = _qkv(p, x, x, cfg, pos, pos,
                           use_rope=not cfg.learned_pos)
    slot = index % S if kind == "local" else index
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k_new.astype(cache_k.dtype), (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v_new.astype(cache_v.dtype), (0, slot, 0, 0))
    sidx = jnp.arange(S)
    if kind == "local":
        held = index - jnp.mod(index - sidx, S)       # absolute pos per slot
        valid = (held >= 0) & (held > index - max(cfg.window, 1)) & \
                (held <= index)
    else:
        valid = sidx <= index
    mask = valid[None, None, :]                        # [1,1,S] -> [B,T,S]
    out = _sdpa(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype),
                mask, cfg)
    y = jnp.einsum("bte,ed->btd", out, p["wo"].astype(cfg.compute_dtype))
    return y, cache_k, cache_v


# --------------------------------------------------------------------------
# Dense MLP (SwiGLU / GeGLU / plain)
# --------------------------------------------------------------------------


def mlp_specs(cfg, d_ff: Optional[int] = None):
    d, f, dt = cfg.d_model, d_ff or cfg.d_ff, cfg.param_dtype
    s = {"wu": fan_in_normal((d, f), 0, dt, ("embed", "mlp")),
         "wd": fan_in_normal((f, d), 0, dt, ("mlp", "embed"))}
    if cfg.gated_mlp:
        s["wg"] = fan_in_normal((d, f), 0, dt, ("embed", "mlp"))
    return s


def _act(x, act: str):
    return jax.nn.gelu(x) if act == "gelu" else jax.nn.silu(x)


def mlp_apply(p, x, cfg):
    cd = cfg.compute_dtype
    h = jnp.einsum("btd,df->btf", x.astype(cd), p["wu"].astype(cd))
    if "wg" in p:
        g = jnp.einsum("btd,df->btf", x.astype(cd), p["wg"].astype(cd))
        h = _act(g, cfg.act) * h
    else:
        h = _act(h, cfg.act)
    h = constrain(h, "batch", None, "mlp")
    y = tp_proj_out(h, p["wd"], cfg)
    return constrain(y, "batch", None, None)


# --------------------------------------------------------------------------
# Mixture of Experts (top-k router, sort-based capacity dispatch, EP over
# the "experts" logical axis). Token-dropping keeps all shapes static.
# --------------------------------------------------------------------------


def moe_specs(cfg):
    d, E, f, dt = cfg.d_model, cfg.num_experts, cfg.moe_d_ff, cfg.param_dtype
    return {
        "router": fan_in_normal((d, E), 0, dt, ("embed", None)),
        "wg": fan_in_normal((d, E, f), 0, dt, ("embed", "experts", "mlp")),
        "wu": fan_in_normal((d, E, f), 0, dt, ("embed", "experts", "mlp")),
        "wd": fan_in_normal((f, E, d), 0, dt, ("mlp", "experts", "embed")),
    }


def moe_capacity(cfg, tokens: int) -> int:
    c = int(np.ceil(tokens * cfg.experts_per_token * cfg.moe_capacity
                    / cfg.num_experts))
    return max(int(np.ceil(c / 8.0)) * 8, 8)


def _moe_dispatch(xf, eid, gate, E, k, C, cd):
    """Sort-based capacity dispatch. xf [n,d]; eid/gate [n,k].
    Returns (buf [E,C,d], st, keep, dest, sg) — metadata for combine."""
    n, d = xf.shape
    flat_e = eid.reshape(-1)                            # [n*k]
    flat_g = gate.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(n), k)
    order = jnp.argsort(flat_e)                         # stable
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.bincount(flat_e, length=E)
    offset = jnp.cumsum(counts) - counts                # segment starts
    pos = jnp.arange(n * k) - offset[se]                # rank within expert
    keep = pos < C
    dest = jnp.where(keep, se * C + pos, E * C)         # E*C = drop slot
    buf = jnp.zeros((E * C + 1, d), cd).at[dest].set(xf[st].astype(cd))
    return buf[:-1].reshape(E, C, d), st, keep, dest, sg


def _moe_combine(y, st, keep, dest, sg, n, d, cd):
    """Inverse of dispatch: y [E*C, d] -> [n, d] weighted by gates."""
    gathered = jnp.where(keep[:, None], y[jnp.where(keep, dest, 0)], 0.0)
    return jnp.zeros((n, d), cd).at[st].add(
        gathered * sg[:, None].astype(cd))


def _expert_ffn(p, buf, cfg):
    cd = cfg.compute_dtype
    buf = constrain(buf, "experts", None, None)
    h_g = jnp.einsum("ecd,def->ecf", buf, p["wg"].astype(cd))
    h_u = jnp.einsum("ecd,def->ecf", buf, p["wu"].astype(cd))
    h = _act(h_g, cfg.act) * h_u
    h = constrain(h, "experts", None, "mlp")
    y = jnp.einsum("ecf,fed->ecd", h, p["wd"].astype(cd))
    return constrain(y, "experts", None, None)


def _router(p, xf, cfg):
    cd = cfg.compute_dtype
    logits = jnp.einsum("td,de->te", xf.astype(cd),
                        p["router"].astype(cd)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, cfg.experts_per_token)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return gate, eid


def moe_apply(p, x, cfg):
    """x: [B, T, d] -> [B, T, d].  Aux-loss-free top-k with renormalized
    gates (qwen3/granite style); dropped tokens pass through the residual.

    Two dispatch implementations (cfg.moe_impl, §Perf iteration 2):
      global  one argsort/scatter over ALL tokens. Under GSPMD the global
              sort + scatter against the expert-sharded buffer replicates
              activations (measured 4.4e13 B/dev of all-reduce on
              qwen3-moe-30b train_4k — the worst cell in the fleet).
      local   shard_map over the batch axes: each data shard sorts only its
              own tokens into a LOCAL capacity block (pure index math, no
              collectives); the only cross-shard traffic is the unavoidable
              token<->expert all-to-all around the expert FFN, inserted by
              GSPMD at the 'experts' constraint.
    """
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    cd = cfg.compute_dtype
    n = B * T
    xf = constrain(x.reshape(n, d), "batch", None)

    mesh = active_mesh()
    dp = tuple(a for a in ("pod", "data")
               if mesh is not None and a in mesh.axis_names)
    dp_size = 1
    if mesh is not None:
        sizes = dict(mesh.shape)
        for a in dp:
            dp_size *= sizes[a]

    if cfg.moe_impl != "local" or mesh is None or dp_size == 1 \
            or n % dp_size != 0:
        # -- global path (reference / CPU tests / tiny batches) ------------
        C = moe_capacity(cfg, n)
        gate, eid = _router(p, xf, cfg)
        buf, st, keep, dest, sg = _moe_dispatch(xf, eid, gate, E, k, C, cd)
        y = _expert_ffn(p, buf, cfg).reshape(E * C, d)
        out = _moe_combine(y, st, keep, dest, sg, n, d, cd)
        return constrain(out.reshape(B, T, d), "batch", None, None)

    # -- local path: per-shard dispatch, GSPMD expert FFN ------------------
    n_loc = n // dp_size
    C = moe_capacity(cfg, n_loc)
    gate, eid = _router(p, xf, cfg)
    tok_spec = P(dp if len(dp) > 1 else dp[0])

    def dispatch(xf_blk, eid_blk, gate_blk):
        return _moe_dispatch(xf_blk, eid_blk, gate_blk, E, k, C, cd)

    buf, st, keep, dest, sg = shard_map(
        dispatch, mesh=mesh,
        in_specs=(P(*tok_spec, None), P(*tok_spec, None),
                  P(*tok_spec, None)),
        out_specs=(P(None, *tok_spec, None), tok_spec, tok_spec, tok_spec,
                   tok_spec),
        axis_names=set(dp), check_vma=False)(xf, eid, gate)

    y = _expert_ffn(p, buf, cfg)                 # all-to-all in, ffn, out
    y = constrain(y, None, "batch", None)        # capacity dim back to dp

    def combine(y_blk, st_blk, keep_blk, dest_blk, sg_blk):
        return _moe_combine(y_blk.reshape(E * C, d), st_blk, keep_blk,
                            dest_blk, sg_blk, n_loc, d, cd)

    out = shard_map(
        combine, mesh=mesh,
        in_specs=(P(None, *tok_spec, None), tok_spec, tok_spec, tok_spec,
                  tok_spec),
        out_specs=P(*tok_spec, None),
        axis_names=set(dp), check_vma=False)(y, st, keep, dest, sg)
    return constrain(out.reshape(B, T, d), "batch", None, None)


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------


def embed_specs(cfg):
    dt = cfg.param_dtype
    s = {"embedding": ParamSpec((cfg.vocab_size, cfg.d_model), dt,
                                ("vocab", "embed"), "normal", 0.02)}
    if not cfg.tie_embeddings:
        s["unembed"] = fan_in_normal((cfg.d_model, cfg.vocab_size), 0, dt,
                                     ("embed", "vocab"))
    if cfg.learned_pos:
        s["pos"] = ParamSpec((cfg.max_pos, cfg.d_model), dt,
                             (None, "embed"), "normal", 0.02)
    return s


def embed_apply(p, tokens, cfg, pos0=0):
    x = jnp.take(p["embedding"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.embed_scale:
        x = x * float(np.sqrt(cfg.d_model))
    if cfg.learned_pos:
        T = tokens.shape[1]
        x = x + jax.lax.dynamic_slice_in_dim(
            p["pos"], pos0, T, 0).astype(cfg.compute_dtype)[None]
    return constrain(x, "batch", None, None)


def unembed_apply(p, x, cfg):
    cd = cfg.compute_dtype
    if cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", x.astype(cd),
                            p["embedding"].astype(cd))
    else:
        logits = jnp.einsum("btd,dv->btv", x.astype(cd),
                            p["unembed"].astype(cd))
    return constrain(logits.astype(jnp.float32), "batch", None, "vocab")
