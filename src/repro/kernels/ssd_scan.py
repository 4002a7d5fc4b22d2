"""Pallas TPU kernel: Mamba2 SSD (state-space duality) chunked scan.

The SSD recurrence (per batch b, head h; Mamba2 §6, arXiv:2405.21060):

    a_t   = exp(dt_t · A_h)                               (scalar decay)
    S_t   = a_t · S_{t-1} + dt_t · B_t ⊗ x_t              (N×P state)
    y_t   = C_t · S_t                                     (P,)

A naive scan is sequential in S (bad for the MXU). The SSD *chunked* form
turns it into dense matmuls: split the sequence into chunks of length Lc;
within a chunk the causal interaction is a (Lc×Lc) decay-masked matmul
(runs on the MXU), while the inter-chunk state is a rank-N carry.

TPU mapping: grid = (B, H, S/Lc) with the **chunk axis innermost** — Pallas
TPU executes grid steps sequentially, so the running state lives in a VMEM
scratch buffer across chunk iterations (reset at chunk 0), exactly like the
(m, l, acc) carry in flash attention. No HBM round-trip for the state.

    x  block (1, Lc, 1, P)      dt block (1, Lc, 1)
    B  block (1, Lc, 1, N)      C  block (1, Lc, 1, N)
    A  block (1,)               y  block (1, Lc, 1, P)
    scratch: S [N, P] float32

VMEM at Lc=128, N=128, P=64: ~0.4 MB. The (Lc, Lc) intra-chunk matmul and
the (Lc, N)x(N, P) inter-chunk matmuls are MXU-aligned at these tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # [Lc, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # [Lc]
    A = a_ref[0].astype(jnp.float32)                 # scalar (per head)
    B = b_ref[0, :, 0, :].astype(jnp.float32)        # [Lc, N]
    C = c_ref[0, :, 0, :].astype(jnp.float32)        # [Lc, N]

    a = dt * A                                       # log-decay per step
    cum = jnp.cumsum(a)                              # [Lc]
    # L[i, j] = exp(cum_i - cum_j) for i >= j else 0  (segment-sum mask)
    li = cum[:, None] - cum[None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    L = jnp.where(causal, jnp.exp(jnp.where(causal, li, 0.0)), 0.0)

    # intra-chunk: M[i,j] = (C_i · B_j) L[i,j] dt_j ;  y_intra = M @ x
    cb = jnp.dot(C, B.T, preferred_element_type=jnp.float32)  # [Lc, Lc]
    M = cb * L * dt[None, :]
    y = jnp.dot(M, x, preferred_element_type=jnp.float32)     # [Lc, P]

    # inter-chunk: y_i += exp(cum_i) · (C_i @ S_in)
    S_in = state_ref[...]                                     # [N, P]
    y = y + jnp.exp(cum)[:, None] * jnp.dot(
        C, S_in, preferred_element_type=jnp.float32)

    # state update: S_out = exp(total)·S_in + Σ_j exp(total-cum_j)·dt_j·B_j⊗x_j
    total = cum[-1]
    w = jnp.exp(total - cum) * dt                             # [Lc]
    S_out = jnp.exp(total) * S_in + jnp.dot(
        (B * w[:, None]).T, x, preferred_element_type=jnp.float32)
    state_ref[...] = S_out

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x: jax.Array, dt: jax.Array, A: jax.Array,
                    B: jax.Array, C: jax.Array, *, chunk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Chunked SSD over [Bt, S, H, P] inputs.

    x:  [Bt, S, H, P]   dt: [Bt, S, H]   A: [H]
    B:  [Bt, S, H, N]   C:  [Bt, S, H, N]     (per-head; wrappers expand
                                               grouped B/C to heads)
    Returns y: [Bt, S, H, P]. S is padded to a chunk multiple (dt padding
    is zero ⇒ identity decay, zero contribution — exactness preserved).
    """
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    ps = (-S) % chunk
    if ps:
        x = jnp.pad(x, ((0, 0), (0, ps), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, ps), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, ps), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, ps), (0, 0), (0, 0)))
    Sp = S + ps

    kern = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kern,
        grid=(Bt, H, Sp // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, c: (b, c, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((Bt, Sp, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C)
    return y[:, :S]
