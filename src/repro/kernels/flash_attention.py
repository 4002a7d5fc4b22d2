"""Pallas TPU kernel: FlashAttention (streaming-softmax attention).

Used by every attention-bearing assigned architecture (GQA / MLA-decoded /
SWA / cross-attention all reduce to this primitive after head expansion).
Standard online-softmax recurrence with the KV axis innermost in the grid
so the running (m, l, acc) state lives in VMEM scratch across KV blocks:

    grid = (B*H, Sq/bq, Skv/bk)           # kv innermost
    q block (1, bq, D), k/v blocks (1, bk, D), out (1, bq, D)
    scratch: m [bq,1], l [bq,1], acc [bq, D]   (float32)

Causal and sliding-window (SWA) masking are static specializations; fully
masked KV blocks are skipped with ``pl.when`` (block-level causal skip) —
on hardware this halves causal-attention work, and the same predicate
implements the O(S·W) sliding-window cost for `h2o-danube-3-4b`.

VMEM at bq=bk=128, D=128: q/k/v/out 64 KB each + scratch ~130 KB ≈ 0.4 MB.
MXU dims (bq, bk, D) are all multiples of 128 for head_dim 128 archs; the
wrapper pads smaller head dims (80/120) up to 128.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, kv_len: int,
                  q_offset: int, bq: int, bk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level skip: with causal/window masking some KV blocks are
    # entirely masked for this query block
    row_hi = q_offset + qi * bq + bq - 1          # last query position
    row_lo = q_offset + qi * bq                   # first query position
    col_lo = ki * bk
    col_hi = ki * bk + bk - 1
    run = jnp.asarray(True)
    if causal:
        run = run & (col_lo <= row_hi)
    if window > 0:
        run = run & (col_hi >= row_lo - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [bq, D]
        k = k_ref[0].astype(jnp.float32)          # [bk, D]
        v = v_ref[0].astype(jnp.float32)          # [bk, D]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        rows = q_offset + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < kv_len
        if causal:
            mask = mask & (cols <= rows)
        if window > 0:
            mask = mask & (cols >= rows - window + 1)
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[...]                       # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "window", "bq", "bk", "q_offset", "interpret"))
def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = False, scale: float | None = None, window: int = 0,
    bq: int = 128, bk: int = 128, q_offset: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Attention over [B, H, S, D] tensors.

    ``window > 0`` enables sliding-window masking (implies causal-style
    locality: position i attends to [i-window+1, i]); combine with
    ``causal=True`` for autoregressive SWA. ``q_offset`` positions the
    query block within the KV sequence (decode: q_offset = kv_len - Sq).
    """
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    pq = (-Sq) % bq
    pk = (-Skv) % bk
    pd = (-D) % 128 if D > 128 else (128 - D if D < 128 else 0)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, pd)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, pd)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, pd)))
    Sqp, Skvp, Dp = Sq + pq, Skv + pk, D + pd

    qf = qp.reshape(B * H, Sqp, Dp)
    kf = kp.reshape(B * H, Skvp, Dp)
    vf = vp.reshape(B * H, Skvp, Dp)

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        kv_len=Skv, q_offset=q_offset, bq=bq, bk=bk)
    out = pl.pallas_call(
        kern,
        grid=(B * H, Sqp // bq, Skvp // bk),
        in_specs=[
            pl.BlockSpec((1, bq, Dp), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, Dp), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, Dp), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dp), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, Dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dp), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sqp, Dp)[:, :, :Sq, :D]
