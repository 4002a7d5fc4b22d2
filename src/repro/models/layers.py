"""Composable transformer/SSM building blocks for all assigned archs.

Conventions
-----------
* params: nested dicts; leaves carry the layer's weights in
  ``cfg.param_dtype`` (bf16 for the large models). Activations are bf16
  with f32 softmax/norm/SSD accumulation.
* every ``*_init`` takes an rng key and returns params; every ``*_apply``
  is pure. Blocks that participate in the layer scan are shape-uniform.
* attention uses a **blockwise streaming-softmax core** (q- and kv-
  chunked ``lax.scan``) — memory O(S·chunk) instead of O(S²), which is
  what lets prefill_32k and the 500k decode fit the dry-run memory
  budget. On real TPU the Pallas flash kernel
  (``repro.kernels.flash_attention``) implements the same contraction;
  the jnp core is its SPMD-partitionable twin (same math, same masking).
* the MoE block has two equivalent implementations: a single-device
  dispatch (smoke tests) and a shard_map expert-parallel dispatch with
  explicit all_to_all (production; see ``repro.sharding``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import nn
from .config import ArchConfig, MLAConfig, MoEConfig, SSMConfig
from .parallel import ParallelCtx

_NULL_CTX = ParallelCtx()

Params = Dict[str, Any]


def _dt(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_cos_sin(positions: jnp.ndarray, dim: int, theta: float,
                 dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions [...,S] → cos/sin [..., S, dim/2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                / dim))
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               fraction: float = 1.0) -> jnp.ndarray:
    """Rotate the first ``fraction`` of the head dim. x: [B,S,H,D]."""
    D = x.shape[-1]
    rd = int(D * fraction)
    rd -= rd % 2
    if rd == 0:
        return x
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    c = cos[..., :rd // 2][:, :, None, :]
    s = sin[..., :rd // 2][:, :, None, :]
    y1 = (x1 * c - x2 * s).astype(x.dtype)
    y2 = (x2 * c + x1 * s).astype(x.dtype)
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr, xp], axis=-1) if rd < D else yr


# ---------------------------------------------------------------------------
# blockwise attention core (jnp twin of the Pallas flash kernel)
# ---------------------------------------------------------------------------
#
# Training uses a CUSTOM VJP: plain autodiff through the kv-chunk scan
# stores every chunk's probability block — O(S²) residuals (measured:
# 34 GB/device on deepseek train_4k), which defeats flash attention's
# purpose. The custom backward recomputes p per chunk from (q, k, lse),
# exactly like the Pallas/TPU kernel's two-pass backward: memory drops to
# O(S·chunk) and compute grows by one extra forward pass — the standard
# flash trade.


def _mask_for(rows, cols, kv_valid, causal: bool, window: int):
    m = (cols[None, :] >= 0) & (cols[None, :] < kv_valid)
    if causal:
        m = m & (cols[None, :] <= rows[:, None])
    if window > 0:
        m = m & (cols[None, :] >= rows[:, None] - window + 1)
    return m


def _flash_fwd_chunks(qs, ks, vs, q_off, kv_off, Skv, causal, window,
                      scale, with_lse: bool):
    """qs: [nq,B,qc,g,r,D]  ks/vs: [nk,B,kc,g,D*] → out [nq,B,qc,g,r,Dv]
    (+ lse [nq,B,g,r,qc])."""
    nq, B, qc, g, r, D = qs.shape
    nk, _, kc, _, Dv = vs.shape
    kv_valid = kv_off + Skv

    def q_block(qi_and_blk):
        qi, qblk = qi_and_blk
        qblk = qblk.astype(jnp.float32)
        rows = q_off + qi * qc + jnp.arange(qc)

        def kv_step(carry, kj_and_kv):
            m, l, acc = carry
            kj, kblk, vblk = kj_and_kv
            cols = kv_off + kj * kc + jnp.arange(kc)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qblk,
                           kblk.astype(jnp.float32)) * scale
            mask = _mask_for(rows, cols, kv_valid, causal, window)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, g, r, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((B, g, r, qc), jnp.float32)
        a0 = jnp.zeros((B, g, r, qc, Dv), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  (jnp.arange(nk), ks, vs))
        lsafe = jnp.maximum(l, 1e-20)
        out = acc / lsafe[..., None]
        lse = m + jnp.log(lsafe)                       # [B,g,r,qc]
        # → [B, qc, g, r, Dv]
        return out.transpose(0, 3, 1, 2, 4), lse

    if nq == 1:
        o, s = q_block((jnp.asarray(0, jnp.int32), qs[0]))
        outs, lses = o[None], s[None]
    else:
        outs, lses = lax.map(q_block, (jnp.arange(nq), qs))
    return (outs, lses) if with_lse else (outs, None)


def _make_flash(causal: bool, window: int, q_off: int, kv_off: int,
                Skv: int, scale: float):
    """Custom-VJP flash attention over pre-chunked layouts (static
    offsets — the training/prefill path)."""

    @jax.custom_vjp
    def flash(qs, ks, vs):
        out, _ = _flash_fwd_chunks(qs, ks, vs, q_off, kv_off, Skv,
                                   causal, window, scale, False)
        return out

    def fwd(qs, ks, vs):
        out, lse = _flash_fwd_chunks(qs, ks, vs, q_off, kv_off, Skv,
                                     causal, window, scale, True)
        return out, (qs, ks, vs, out, lse)

    def bwd(res, dout):
        qs, ks, vs, outs, lses = res
        nq, B, qc, g, r, D = qs.shape
        nk, _, kc, _, Dv = vs.shape
        kv_valid = kv_off + Skv

        # delta_i = Σ_v dout_i · out_i   [nq, B, g, r, qc]
        delta = jnp.einsum("nbqgrv,nbqgrv->nbgrq",
                           dout.astype(jnp.float32),
                           outs.astype(jnp.float32))

        def kv_block(kj_and_blk):
            kj, kblk, vblk = kj_and_blk
            kf = kblk.astype(jnp.float32)
            vf = vblk.astype(jnp.float32)
            cols = kv_off + kj * kc + jnp.arange(kc)

            def q_step(carry, inp):
                dk_acc, dv_acc = carry
                qi, qblk, doblk, lseblk, dblk = inp
                qf = qblk.astype(jnp.float32)
                dof = doblk.astype(jnp.float32)
                rows = q_off + qi * qc + jnp.arange(qc)
                s = jnp.einsum("bqgrd,bkgd->bgrqk", qf, kf) * scale
                mask = _mask_for(rows, cols, kv_valid, causal, window)
                p = jnp.exp(s - lseblk[..., None])
                p = jnp.where(mask[None, None, None], p, 0.0)
                dv_acc = dv_acc + jnp.einsum("bgrqk,bqgrv->bkgv", p, dof)
                dp = jnp.einsum("bqgrv,bkgv->bgrqk", dof, vf)
                ds = p * (dp - dblk[..., None]) * scale
                dq_c = jnp.einsum("bgrqk,bkgd->bqgrd", ds, kf)
                dk_acc = dk_acc + jnp.einsum("bgrqk,bqgrd->bkgd", ds, qf)
                return (dk_acc, dv_acc), dq_c

            dk0 = jnp.zeros((B, kc, g, D), jnp.float32)
            dv0 = jnp.zeros((B, kc, g, Dv), jnp.float32)
            (dk, dv), dq_parts = lax.scan(
                q_step, (dk0, dv0),
                (jnp.arange(nq), qs, dout, lses, delta))
            return dk, dv, dq_parts                  # dq_parts [nq,...]

        if nk == 1:
            dk, dv, dqp = kv_block(
                (jnp.asarray(0, jnp.int32), ks[0], vs[0]))
            dks, dvs, dq = dk[None], dv[None], dqp
        else:
            dks, dvs, dqps = lax.map(
                kv_block, (jnp.arange(nk), ks, vs))
            dq = dqps.sum(axis=0)                    # Σ over kv chunks
        return (dq.astype(qs.dtype), dks.astype(ks.dtype),
                dvs.astype(vs.dtype))

    flash.defvjp(fwd, bwd)
    return flash


def blockwise_attention(
    q: jnp.ndarray,            # [B, Sq, H, D]
    k: jnp.ndarray,            # [B, Skv, Hkv, D]
    v: jnp.ndarray,            # [B, Skv, Hkv, Dv]
    *, causal: bool, window: int = 0, q_offset=0, kv_offset=0,
    q_chunk: int = 2048, kv_chunk: int = 1024,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    rep = H // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    # pad seq dims to chunk multiples
    pq = (-Sq) % q_chunk
    pk = (-Skv) % kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    nq, nk = (Sq + pq) // q_chunk, (Skv + pk) // kv_chunk

    # [nq, B, qc, Hkv, rep, D]
    qs = qp.reshape(B, nq, q_chunk, Hkv, rep, D).transpose(1, 0, 2, 3, 4, 5)
    ks = kp.reshape(B, nk, kv_chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(B, nk, kv_chunk, Hkv, Dv).transpose(1, 0, 2, 3, 4)

    if isinstance(q_offset, int) and isinstance(kv_offset, int):
        # static offsets (train / prefill): differentiable custom-VJP path
        flash = _make_flash(causal, window, q_offset, kv_offset, Skv, scale)
        out = flash(qs, ks, vs)
    else:
        # traced offsets (decode with a moving cache index): forward-only
        out, _ = _flash_fwd_chunks(
            qs, ks, vs, jnp.asarray(q_offset, jnp.int32),
            jnp.asarray(kv_offset, jnp.int32), Skv, causal, window,
            scale, False)
    # [nq, B, qc, g, r, Dv] → [B, Sq, H, Dv]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq + pq, H, Dv)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (covers dense / SWA / encoder / qkv-bias variants)
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ArchConfig, *, cross: bool = False) -> Params:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    kd = cfg.vision_dim if cross else d
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = _dt(cfg)
    p = {
        "wq": nn.normal_init(k1, (d, cfg.n_heads * hd), 0.02, dt),
        "wk": nn.normal_init(k2, (kd, cfg.n_kv_heads * hd), 0.02, dt),
        "wv": nn.normal_init(k3, (kd, cfg.n_kv_heads * hd), 0.02, dt),
        "wo": nn.normal_init(k4, (cfg.n_heads * hd, d), 0.02, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = nn.zeros((cfg.n_heads * hd,), dt)
        p["bk"] = nn.zeros((cfg.n_kv_heads * hd,), dt)
        p["bv"] = nn.zeros((cfg.n_kv_heads * hd,), dt)
    return p


def attention_apply(
    p: Params, cfg: ArchConfig, x: jnp.ndarray, *,
    positions: jnp.ndarray,                  # [B, S] absolute positions
    cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    cache_index: Optional[jnp.ndarray] = None,
    memory: Optional[jnp.ndarray] = None,    # cross-attn K/V source
    ctx: ParallelCtx = _NULL_CTX,
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """Returns (output, new_cache).

    * training / prefill: ``cache=None`` → full self-attention over x
      (returns freshly-built (k, v) so prefill can seed a decode cache).
    * decode: ``cache=(k, v)`` of shape [B, Smax, Hkv, hd] and
      ``cache_index`` = #valid tokens; x is the new token(s).
    * cross-attention: ``memory`` replaces x as the K/V source (no cache,
      no rope on keys).
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads

    q = x @ p["wq"]
    kv_src = memory if memory is not None else x
    k = kv_src @ p["wk"]
    v = kv_src @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = ctx.heads(q.reshape(B, S, H, hd), H)
    k = ctx.heads(k.reshape(B, kv_src.shape[1], Hkv, hd), Hkv)
    v = ctx.heads(v.reshape(B, kv_src.shape[1], Hkv, hd), Hkv)

    if memory is None and cfg.rope_fraction > 0:
        cos, sin = rope_cos_sin(positions, int(hd * cfg.rope_fraction),
                                cfg.rope_theta)
        q = apply_rope(q, cos, sin, 1.0 if hd == int(
            hd * cfg.rope_fraction) else cfg.rope_fraction)
        k = apply_rope(k, cos, sin, 1.0 if hd == int(
            hd * cfg.rope_fraction) else cfg.rope_fraction)

    new_cache = None
    if memory is not None:
        out = blockwise_attention(q, k, v, causal=False)
    elif cache is None:
        causal = cfg.causal
        out = blockwise_attention(q, k, v, causal=causal,
                                  window=cfg.window)
        new_cache = (k, v)
    elif cfg.window > 0:
        # sliding-window ring cache: keep only the last W positions.
        # Shift-append keeps slots in increasing absolute-position order,
        # so masking stays the standard (causal, window, kv_offset) triple.
        # Attention runs over [ring(W) ++ new(S)] BEFORE truncation so a
        # multi-token step (prefill-through-decode) sees every key still
        # inside some query's window; the stored ring keeps the last W.
        ck, cv = cache
        W = ck.shape[1]
        full_k = jnp.concatenate([ck, k.astype(ck.dtype)], axis=1)
        full_v = jnp.concatenate([cv, v.astype(cv.dtype)], axis=1)
        kv_off = cache_index - W
        out = blockwise_attention(
            q, full_k, full_v, causal=True, window=cfg.window,
            q_offset=cache_index, kv_offset=kv_off)
        new_cache = (full_k[:, -W:], full_v[:, -W:])
    else:
        ck, cv = cache
        ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                      (0, cache_index, 0, 0))
        cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                      (0, cache_index, 0, 0))
        # positions beyond cache_index+S are masked by causality
        out = blockwise_attention(
            q, ck, cv, causal=True, window=cfg.window,
            q_offset=cache_index)
        new_cache = (ck, cv)

    out = ctx.flat_heads(out.reshape(B, S, H * hd), H * hd)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (deepseek-v2)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ArchConfig) -> Params:
    m = cfg.mla
    d = cfg.d_model
    H = cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    keys = jax.random.split(key, 6)
    dt = _dt(cfg)
    return {
        "wq_a": nn.normal_init(keys[0], (d, m.q_lora_rank), 0.02, dt),
        "wq_b": nn.normal_init(keys[1], (m.q_lora_rank, H * qk), 0.02, dt),
        "wkv_a": nn.normal_init(
            keys[2], (d, m.kv_lora_rank + m.qk_rope_dim), 0.02, dt),
        "wkv_b": nn.normal_init(
            keys[3], (m.kv_lora_rank, H * (m.qk_nope_dim + m.v_head_dim)),
            0.02, dt),
        "wo": nn.normal_init(keys[4], (H * m.v_head_dim, d), 0.02, dt),
        "q_norm": nn.rmsnorm_init(m.q_lora_rank, dt),
        "kv_norm": nn.rmsnorm_init(m.kv_lora_rank, dt),
    }


def mla_apply(
    p: Params, cfg: ArchConfig, x: jnp.ndarray, *,
    positions: jnp.ndarray,
    cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    cache_index: Optional[jnp.ndarray] = None,
    ctx: ParallelCtx = _NULL_CTX,
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """MLA attention. Cache stores the *compressed* (c_kv, k_rope) pair —
    (kv_lora_rank + qk_rope_dim) per token instead of 2·H·hd (the paper's
    93 % KV-cache reduction is what makes decode_32k×128 fit)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qk_n, qk_r, dv = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim

    q_a = ctx.constrain(x @ p["wq_a"], ctx.residual_spec(S))
    q = nn.rmsnorm(p["q_norm"], q_a) @ p["wq_b"]
    q = ctx.heads(q.reshape(B, S, H, qk_n + qk_r), H)
    q_nope, q_rope = q[..., :qk_n], q[..., qk_n:]

    kv_a = ctx.constrain(x @ p["wkv_a"],
                         ctx.residual_spec(S))   # [B,S, rank + qk_r]
    c_kv = nn.rmsnorm(p["kv_norm"], kv_a[..., :m.kv_lora_rank])
    k_rope = kv_a[..., m.kv_lora_rank:].reshape(B, S, 1, qk_r)

    cos, sin = rope_cos_sin(positions, qk_r, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)

    if cache is not None:
        # ---- decode: WEIGHT-ABSORBED attention in latent space ---------
        # Decompressing K/V for all T cached positions per step costs
        # O(T·rank·H·(dn+dv)) — 230× the useful work at T=32k. DeepSeek's
        # absorption trick folds W_uk into the query and W_uv into the
        # output: attention runs MQA-style over the 576-dim latent, the
        # cache is never decompressed.
        cc, cr = cache
        cc = lax.dynamic_update_slice(cc, c_kv.astype(cc.dtype),
                                      (0, cache_index, 0))
        cr = lax.dynamic_update_slice(cr, k_rope.astype(cr.dtype),
                                      (0, cache_index, 0, 0))
        new_cache = (cc, cr)
        rank = m.kv_lora_rank
        w_uk = p["wkv_b"][:, :].reshape(rank, H, qk_n + dv)[..., :qk_n]
        w_uv = p["wkv_b"][:, :].reshape(rank, H, qk_n + dv)[..., qk_n:]
        # q into latent space: [B,S,H,rank]
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
        qf = jnp.concatenate([q_lat, q_rope], axis=-1)
        k_lat = jnp.concatenate(
            [cc[:, :, None, :], cr.astype(cc.dtype)], axis=-1)  # [B,T,1,·]
        out_lat = blockwise_attention(
            qf, k_lat, cc[:, :, None, :], causal=cfg.causal,
            q_offset=cache_index, scale=1.0 / math.sqrt(qk_n + qk_r))
        # back to value heads: [B,S,H,dv]
        out = jnp.einsum("bshr,rhd->bshd", out_lat, w_uv)
        out = out.reshape(B, S, H * dv)
        return out @ p["wo"], new_cache

    # ---- train / prefill: materialized heads (dense matmuls, MXU) ------
    kv = c_kv @ p["wkv_b"]
    T = kv.shape[1]
    kv = ctx.heads(kv.reshape(B, T, H, qk_n + dv), H)
    k_nope, v = kv[..., :qk_n], kv[..., qk_n:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, T, H, qk_r))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = blockwise_attention(
        qf, k, v, causal=cfg.causal,
        scale=1.0 / math.sqrt(qk_n + qk_r))
    out = ctx.flat_heads(out.reshape(B, S, H * dv), H * dv)
    return out @ p["wo"], (c_kv, k_rope)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ArchConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = _dt(cfg)
    return {"wg": nn.normal_init(k1, (d, f), 0.02, dt),
            "wu": nn.normal_init(k2, (d, f), 0.02, dt),
            "wd": nn.normal_init(k3, (f, d), 0.02, dt)}


def mlp_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ArchConfig) -> Params:
    mo = cfg.moe
    d = cfg.d_model
    keys = jax.random.split(key, 3)
    dt = _dt(cfg)
    p = {
        "router": nn.normal_init(keys[0], (d, mo.n_experts), 0.006,
                                 jnp.float32),
        "experts": {
            "wg": nn.normal_init(keys[1], (mo.n_experts, d, mo.d_expert),
                                 0.02, dt),
            "wu": nn.normal_init(keys[1], (mo.n_experts, d, mo.d_expert),
                                 0.02, dt),
            "wd": nn.normal_init(keys[2], (mo.n_experts, mo.d_expert, d),
                                 0.02, dt),
        },
    }
    if mo.n_shared:
        p["shared"] = mlp_init(keys[2], cfg, d_ff=mo.n_shared * mo.d_expert)
    return p


def _route(router_w, x_flat, mo: MoEConfig):
    """→ (probs [T,k], ids [T,k], aux_loss).

    Softmax scores; with ``n_group > 1`` only the experts of each token's
    ``topk_group`` best groups (a group ranks by its best expert) stay
    eligible. The top-k gate weights are renormalised
    (``norm_topk_prob``) or scaled by ``routed_scaling_factor``."""
    logits = (x_flat.astype(jnp.float32) @ router_w)
    probs_all = jax.nn.softmax(logits, axis=-1)
    scores = probs_all
    if mo.n_group > 1:
        grouped = scores.reshape(scores.shape[0], mo.n_group, -1)
        _, top_groups = lax.top_k(grouped.max(axis=-1), mo.topk_group)
        keep = jax.nn.one_hot(top_groups, mo.n_group,
                              dtype=scores.dtype).sum(axis=1)
        scores = (grouped * keep[:, :, None]).reshape(scores.shape)
    probs, ids = lax.top_k(scores, mo.top_k)
    if mo.norm_topk_prob:
        probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    else:
        probs = probs * mo.routed_scaling_factor
    # load-balancing aux loss (Switch-style)
    me = probs_all.mean(axis=0)
    ce = jnp.zeros((mo.n_experts,), jnp.float32).at[ids.reshape(-1)].add(
        1.0) / ids.size
    aux = mo.n_experts * jnp.sum(me * ce)
    return probs, ids, aux


def moe_apply_local(p: Params, cfg: ArchConfig,
                    x_flat: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device capacity-based dispatch (the semantic reference).

    x_flat: [T, D] → ([T, D], aux_loss). Token replicas beyond an
    expert's capacity are dropped (standard dropping MoE).
    """
    mo = cfg.moe
    T, D = x_flat.shape
    probs, ids, aux = _route(p["router"], x_flat, mo)
    cap = int(math.ceil(T * mo.top_k / mo.n_experts * mo.capacity_factor))

    flat_ids = ids.reshape(-1)                                  # [T*k]
    onehot = jax.nn.one_hot(flat_ids, mo.n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                        # position
    pos = jnp.take_along_axis(pos, flat_ids[:, None], axis=1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, flat_ids * cap + pos, mo.n_experts * cap)

    x_rep = jnp.repeat(x_flat, mo.top_k, axis=0)
    buf = jnp.zeros((mo.n_experts * cap + 1, D), x_flat.dtype)
    buf = buf.at[slot].add(x_rep * keep[:, None].astype(x_flat.dtype))
    buf = buf[:-1].reshape(mo.n_experts, cap, D)

    e = p["experts"]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, e["wg"])) * \
        jnp.einsum("ecd,edf->ecf", buf, e["wu"])
    y_buf = jnp.einsum("ecf,efd->ecd", h, e["wd"])
    y_rep = y_buf.reshape(-1, D)[jnp.minimum(slot, mo.n_experts * cap - 1)]
    y_rep = y_rep * keep[:, None].astype(y_rep.dtype)
    w = probs.reshape(-1)[:, None].astype(x_flat.dtype)
    y = (y_rep.astype(x_flat.dtype) * w).reshape(
        T, mo.top_k, D).sum(axis=1)

    if mo.n_shared:
        y = y + mlp_apply(p["shared"], x_flat)
    return y.astype(x_flat.dtype), aux


def moe_apply_ep(p: Params, cfg: ArchConfig, x_flat: jnp.ndarray,
                 axis_name: str, n_shards: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel dispatch inside shard_map.

    Called per-device: ``x_flat`` is this device's token slice [T_loc, D];
    experts are sharded over ``axis_name`` (E_loc = E / n_shards each, the
    leading axis of ``p['experts']`` leaves is already the local slice).
    Protocol: route → bucket by owner shard → all_to_all → local grouped
    matmul → all_to_all back → weighted combine.
    """
    mo = cfg.moe
    T, D = x_flat.shape
    e_loc = mo.n_experts // n_shards
    probs, ids, aux = _route(p["router"], x_flat, mo)
    aux = lax.pmean(aux, axis_name)

    # sender-side capacity per (this device → target shard)
    cap = int(math.ceil(T * mo.top_k / n_shards * mo.capacity_factor))
    flat_ids = ids.reshape(-1)                      # [T*k] global expert id
    owner = flat_ids // e_loc                       # target shard
    onehot = jax.nn.one_hot(owner, n_shards, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos, owner[:, None], axis=1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, owner * cap + pos, n_shards * cap)

    x_rep = jnp.repeat(x_flat, mo.top_k, axis=0)
    send = jnp.zeros((n_shards * cap + 1, D), x_flat.dtype)
    send = send.at[slot].add(x_rep * keep[:, None].astype(x_flat.dtype))
    send = send[:-1].reshape(n_shards, cap, D)
    # local expert index of each sent replica (+1; 0 = invalid)
    lid = jnp.zeros((n_shards * cap + 1,), jnp.int32)
    lid = lid.at[slot].add(
        jnp.where(keep, (flat_ids % e_loc) + 1, 0).astype(jnp.int32))
    lid = lid[:-1].reshape(n_shards, cap)

    recv = lax.all_to_all(send, axis_name, 0, 0, tiled=False)
    rid = lax.all_to_all(lid, axis_name, 0, 0, tiled=False)
    # recv: [n_shards, cap, D] tokens now living on the owning shard
    rflat = recv.reshape(-1, D)
    idflat = rid.reshape(-1)                        # 0=invalid, else lid+1

    cap_loc = int(math.ceil(
        T * mo.top_k / e_loc * mo.capacity_factor))
    on2 = jax.nn.one_hot(idflat, e_loc + 1, dtype=jnp.int32)
    pos2 = jnp.cumsum(on2, axis=0) - 1
    pos2 = jnp.take_along_axis(pos2, idflat[:, None], axis=1)[:, 0]
    valid = (idflat > 0) & (pos2 < cap_loc)
    slot2 = jnp.where(valid, (idflat - 1) * cap_loc + pos2,
                      e_loc * cap_loc)
    buf = jnp.zeros((e_loc * cap_loc + 1, D), x_flat.dtype)
    buf = buf.at[slot2].add(rflat * valid[:, None].astype(rflat.dtype))
    buf = buf[:-1].reshape(e_loc, cap_loc, D)

    e = p["experts"]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, e["wg"])) * \
        jnp.einsum("ecd,edf->ecf", buf, e["wu"])
    y_buf = jnp.einsum("ecf,efd->ecd", h, e["wd"]).reshape(-1, D)

    y_back = jnp.where(
        valid[:, None], y_buf[jnp.minimum(slot2, e_loc * cap_loc - 1)], 0.0)
    y_send = y_back.reshape(n_shards, cap, D)
    y_recv = lax.all_to_all(y_send, axis_name, 0, 0, tiled=False)
    y_rep = y_recv.reshape(-1, D)[jnp.minimum(slot, n_shards * cap - 1)]
    y_rep = y_rep * keep[:, None].astype(y_rep.dtype)
    w = probs.reshape(-1)[:, None].astype(x_flat.dtype)
    y = (y_rep.astype(x_flat.dtype) * w).reshape(
        T, mo.top_k, D).sum(axis=1)

    if mo.n_shared:
        y = y + mlp_apply(p["shared"], x_flat)
    return y.astype(x_flat.dtype), aux


def moe_apply_tp(p: Params, cfg: ArchConfig, x_flat: jnp.ndarray,
                 axis_name: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Intra-expert tensor-parallel dispatch inside shard_map.

    For MoEs whose expert count doesn't divide the model axis (grok-1:
    8 experts on a 16-way axis). Tokens are sharded over *all* mesh axes;
    every device dispatches its local tokens to all experts, computes with
    its ``d_expert / tp`` weight slice, and a single psum over the model
    axis completes the down-projection contraction.
    """
    mo = cfg.moe
    T, D = x_flat.shape
    probs, ids, aux = _route(p["router"], x_flat, mo)
    cap = int(math.ceil(T * mo.top_k / mo.n_experts * mo.capacity_factor))

    flat_ids = ids.reshape(-1)
    onehot = jax.nn.one_hot(flat_ids, mo.n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos, flat_ids[:, None], axis=1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, flat_ids * cap + pos, mo.n_experts * cap)

    x_rep = jnp.repeat(x_flat, mo.top_k, axis=0)
    buf = jnp.zeros((mo.n_experts * cap + 1, D), x_flat.dtype)
    buf = buf.at[slot].add(x_rep * keep[:, None].astype(x_flat.dtype))
    buf = buf[:-1].reshape(mo.n_experts, cap, D)

    e = p["experts"]                      # wg/wu: [E, D, F/tp], wd: [E, F/tp, D]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, e["wg"])) * \
        jnp.einsum("ecd,edf->ecf", buf, e["wu"])
    y_buf = jnp.einsum("ecf,efd->ecd", h, e["wd"])
    y_buf = lax.psum(y_buf, axis_name)    # complete the F contraction
    y_rep = y_buf.reshape(-1, D)[jnp.minimum(slot, mo.n_experts * cap - 1)]
    y_rep = y_rep * keep[:, None].astype(y_rep.dtype)
    w = probs.reshape(-1)[:, None].astype(x_flat.dtype)
    y = (y_rep.astype(x_flat.dtype) * w).reshape(
        T, mo.top_k, D).sum(axis=1)

    if mo.n_shared:
        y = y + mlp_apply(p["shared"], x_flat)
    return y.astype(x_flat.dtype), aux


# ---------------------------------------------------------------------------
# Mamba2 block (SSD)
# ---------------------------------------------------------------------------

def mamba2_init(key, cfg: ArchConfig) -> Params:
    """Projections are kept *separate* (z / x / B / C / dt and per-stream
    convs) rather than one fused ``in_proj``: slicing a contiguous
    model-sharded axis at non-shard-aligned boundaries forces GSPMD to
    all-gather the full activation every layer (measured: ~1 TB/device of
    spurious collectives on the 370m train cell). Separate weights give
    every stream a clean sharding: x/dt head-sharded, B/C replicated
    (they're n_groups·d_state ≈ tiny)."""
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    g = s.n_groups
    gn = g * s.d_state
    keys = jax.random.split(key, 8)
    dt = _dt(cfg)
    return {
        "wz": nn.normal_init(keys[0], (d, di), 0.02, dt),
        "wx": nn.normal_init(keys[1], (d, di), 0.02, dt),
        "wb": nn.normal_init(keys[2], (d, gn), 0.02, dt),
        "wc": nn.normal_init(keys[3], (d, gn), 0.02, dt),
        "wdt": nn.normal_init(keys[4], (d, nh), 0.02, dt),
        "conv_x": nn.normal_init(keys[5], (s.d_conv, di), 0.02, dt),
        "conv_xb": nn.zeros((di,), dt),
        "conv_bw": nn.normal_init(keys[6], (s.d_conv, gn), 0.02, dt),
        "conv_bb": nn.zeros((gn,), dt),
        "conv_cw": nn.normal_init(keys[7], (s.d_conv, gn), 0.02, dt),
        "conv_cb": nn.zeros((gn,), dt),
        "dt_bias": nn.zeros((nh,), jnp.float32),
        "A_log": nn.normal_init(keys[2], (nh,), 0.1, jnp.float32),
        "D": nn.ones((nh,), jnp.float32),
        "norm": nn.rmsnorm_init(di, dt),
        "out_proj": nn.normal_init(keys[3], (di, d), 0.02, dt),
    }


def _causal_dwconv(x, w, b, state=None):
    """Depthwise causal conv1d: x [B,S,C], w [K,C] → ([B,S,C], new_state).

    ``state`` carries the last K-1 inputs for decode continuity.
    """
    K = w.shape[0]
    S = x.shape[1]
    if state is None:
        padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        padded = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    new_state = padded[:, -(K - 1):] if K > 1 else None
    # K shifted multiplies (K≤4) instead of a stacked [B,S,K,C] window
    # tensor — linear in (padded, w), so autodiff saves neither the stack
    # nor per-tap products (measured 8 GB/device of f32 saves otherwise)
    y = None
    for i in range(K):
        t = padded[:, i:i + S] * w[i]
        y = t if y is None else y + t
    return y + b, new_state


def _ssd_chunked(x, dt, A, B, C, chunk: int, s0=None):
    """Chunked SSD in pure jnp (SPMD-partitionable twin of the Pallas
    kernel; heads shard over the model axis, batch over data).

    x: [Bt,S,H,P] dt: [Bt,S,H] A: [H] B,C: [Bt,S,H,N] → y, last_state.
    ``s0``: initial [Bt,H,N,P] state (prefill continuation).
    """
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (S + pad) // chunk
    xc = x.reshape(Bt, nc, chunk, H, P).astype(jnp.float32)
    dtc = dt.reshape(Bt, nc, chunk, H).astype(jnp.float32)
    Bc = B.reshape(Bt, nc, chunk, H, N).astype(jnp.float32)
    Cc = C.reshape(Bt, nc, chunk, H, N).astype(jnp.float32)

    a = dtc * A[None, None, None, :]                   # [Bt,nc,Lc,H]
    cum = jnp.cumsum(a, axis=2)
    L = jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :])
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(tri[None, None, :, :, None], L, 0.0)

    cb = jnp.einsum("bnihd,bnjhd->bnijh", Cc, Bc)       # (C_i · B_j)
    M = cb * L * dtc[:, :, None, :, :]
    y_intra = jnp.einsum("bnijh,bnjhp->bnihp", M, xc)

    # per-chunk summaries
    total = cum[:, :, -1, :]                            # [Bt,nc,H]
    w = jnp.exp(total[:, :, None, :] - cum) * dtc       # [Bt,nc,Lc,H]
    chunk_state = jnp.einsum("bnlh,bnlhd,bnlhp->bnhdp", w, Bc, xc)

    # inter-chunk scan over nc (sequential, nc = S/chunk steps)
    def step(s_prev, inp):
        tot, cst = inp                                   # [Bt,H], [Bt,H,N,P]
        s_new = s_prev * jnp.exp(tot)[..., None, None] + cst
        return s_new, s_prev

    if s0 is None:
        s0 = jnp.zeros((Bt, H, N, P), jnp.float32)
    last, states_in = lax.scan(
        step, s0, (jnp.moveaxis(total, 1, 0), jnp.moveaxis(chunk_state, 1, 0)))
    states_in = jnp.moveaxis(states_in, 0, 1)            # [Bt,nc,H,N,P]

    y_inter = jnp.einsum("bnlhd,bnhdp->bnlhp", Cc, states_in) * \
        jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(Bt, S + pad, H, P)[:, :S]
    return y, last


def mamba2_apply(
    p: Params, cfg: ArchConfig, x: jnp.ndarray, *,
    cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """Mamba2 block. cache = (conv_state [B, d_conv-1, conv_dim],
    ssd_state [B, H, N, P]) for decode; None for train/prefill."""
    s = cfg.ssm
    B_, S, d = x.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    g = s.n_groups
    N = s.d_state

    z = x @ p["wz"]
    xs = x @ p["wx"]
    bs = x @ p["wb"]
    cs = x @ p["wc"]
    dt_raw = x @ p["wdt"]

    # causal depthwise convs, one per stream (clean per-stream sharding)
    st_x = st_b = st_c = None
    if cache is not None:
        st_x, st_b, st_c = cache[0]
    xs, ns_x = _causal_dwconv(xs, p["conv_x"], p["conv_xb"], st_x)
    bs, ns_b = _causal_dwconv(bs, p["conv_bw"], p["conv_bb"], st_b)
    cs, ns_c = _causal_dwconv(cs, p["conv_cw"], p["conv_cb"], st_c)
    new_conv_state = (ns_x, ns_b, ns_c)
    xs, bs, cs = jax.nn.silu(xs), jax.nn.silu(bs), jax.nn.silu(cs)

    x_ssd = xs.reshape(B_, S, nh, s.head_dim)
    Bmat = bs.reshape(B_, S, g, N)
    Cmat = cs.reshape(B_, S, g, N)
    # broadcast groups → heads
    hpg = nh // g
    Bh = jnp.repeat(Bmat, hpg, axis=2)
    Ch = jnp.repeat(Cmat, hpg, axis=2)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    if cache is None:
        y, last_state = _ssd_chunked(x_ssd, dt, A, Bh, Ch, s.chunk)
    elif S == 1:
        # true decode: one vectorized state update, no scan
        from ..kernels.ref import ssd_decode_ref
        y_t, last_state = ssd_decode_ref(
            cache[1], x_ssd[:, 0].astype(jnp.float32), dt[:, 0], A,
            Bh[:, 0].astype(jnp.float32), Ch[:, 0].astype(jnp.float32))
        y = y_t[:, None]
    else:
        # prefill-through-decode: chunked scan seeded with the cache
        # state (a 32k-token prompt must NOT unroll 32k decode steps —
        # that's a 32768-op trace; this is the same chunked path as
        # training, one scan of S/chunk steps)
        y, last_state = _ssd_chunked(x_ssd, dt, A, Bh, Ch, s.chunk,
                                     s0=cache[1])

    y = y + x_ssd.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B_, S, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = nn.rmsnorm(p["norm"], y)
    out = y @ p["out_proj"]
    new_cache = None
    if s.d_conv > 1:
        new_cache = (new_conv_state, last_state)
    return out, new_cache
