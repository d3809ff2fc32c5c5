"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid: (B, H, n_chunks) — chunks are the minormost (sequential) dim, so the
inter-chunk recurrent state h (P x N fp32) lives in VMEM scratch and is
carried across chunk steps, while each step does the dense intra-chunk work
on the MXU:

  scores = C_c B_c^T  (L x L)   -> masked by the decay kernel exp(segsum)
  y_diag = (scores * decay) (dt x)_c
  y_off  = C_c h_prev * exp(cumsum dA)
  h      = h * exp(sum dA) + B_c^T (decay_states * dt * x)_c

VMEM working set per step: x/dt/B/C chunks + two L x L fp32 tiles + the
(P, N) state ≈ (256x64 + 2x256x256 + 64x128) x 4B ≈ 0.7 MiB. L (=chunk),
P, N are multiples of 8/128 where the config allows — MXU-aligned.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, dtr_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                h_ref, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)     # (L, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)   # (L, 1) — keep 2D for TPU
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)   # (1, L) — same values
    A = a_ref[pl.program_id(1)]                 # this head's A (SMEM scalar)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)    # (L, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)    # (L, N)

    L = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # inclusive prefix sums of dA as triangular matmuls (Mosaic has no
    # cumsum), once as a column and once as a row, so the (L, L) segment
    # sums below need no transpose
    tril = (row >= col).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    dA = dt * A                                               # (L, 1)
    dA_cum = jnp.dot(tril, dA, precision=hi)                  # (L, 1)
    dA_cum_row = jax.lax.dot_general(
        dt_row * A, tril, (((1,), (1,)), ((), ())), precision=hi)  # (1, L)

    # decay kernel: exp(segsum) lower-triangular
    # segsum convention: sum_{j < t <= i} dA_t = dA_cum[i] - dA_cum[j]
    seg = dA_cum - dA_cum_row
    decay = jnp.where(row >= col, jnp.exp(seg), 0.0)        # (L, L)

    dtx = x * dt                                            # (L, P)

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (L, L)
    y_diag = jax.lax.dot_general(scores * decay, dtx,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (L, P)

    # inter-chunk contribution from carried state
    h_prev = h_ref[...]                                     # (P, N)
    state_decay = jnp.exp(dA_cum)                           # (L, 1)
    y_off = jax.lax.dot_general(Cm * state_decay, h_prev,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (L, P)

    y_ref[0, 0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: h = h * exp(sum dA) + (decay_states * dtx)^T B
    # the chunk total and the suffix sums sum_{t > i} dA_t, again as
    # matmuls: Mosaic cannot broadcast one element of a vector both ways
    P = x.shape[1]
    chunk_decay = jnp.exp(jnp.dot(jnp.ones((P, L), jnp.float32), dA,
                                  precision=hi))            # (P, 1)
    decay_states = jnp.exp(jnp.dot((col > row).astype(jnp.float32), dA,
                                   precision=hi))           # (L, 1)
    hb = jax.lax.dot_general(dtx * decay_states, Bm,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)      # (P, N)
    h_ref[...] = h_prev * chunk_decay + hb

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = h_ref[...]


def ssd_scan(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H) fp32 (post-softplus)
    A: jax.Array,      # (H,) fp32 negative
    B_mat: jax.Array,  # (B, S, G, N)
    C_mat: jax.Array,  # (B, S, G, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N)). S padded to chunk."""
    Bb, S, H, P = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    rep = H // G
    S_orig = S
    if S % chunk:
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_mat = jnp.pad(B_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C_mat = jnp.pad(C_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = x.shape[1]
    nc = S // chunk

    # layouts: (B, H, nc, L, ...) so blocks are contiguous per grid row
    xh = jnp.moveaxis(x, 2, 1).reshape(Bb, H, nc, chunk, P)
    dth = jnp.moveaxis(dt, 2, 1).reshape(Bb, H, nc, chunk, 1).astype(jnp.float32)
    dth_row = dth.reshape(Bb, H, nc, 1, chunk)
    bh = jnp.moveaxis(B_mat, 2, 1).reshape(Bb, G, nc, chunk, N)
    ch = jnp.moveaxis(C_mat, 2, 1).reshape(Bb, G, nc, chunk, N)

    grid = (Bb, H, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)

    y, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0, 0)),
            # A is read per head as a scalar: the whole (H,) vector sits
            # in SMEM (a rank-1 VMEM block of 1 is not a legal TPU tile)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, chunk, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xh, dth, dth_row, A.astype(jnp.float32), bh, ch)
    y = y.reshape(Bb, H, S, P)
    y = jnp.moveaxis(y, 1, 2)[:, :S_orig]                   # (B, S, H, P)
    return y, h_final
