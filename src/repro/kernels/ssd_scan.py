"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid = (B, H, n_chunks); the chunk axis is the innermost SEQUENTIAL axis,
and the running SSM state (head_dim x d_state, fp32) lives in VMEM scratch,
carried across chunk steps — the TPU-native replacement for the GPU
implementation's inter-block shared-memory handoff (DESIGN.md: hardware
adaptation).  Within a chunk the computation is the quadratic 'dual' form:
two small matmuls that map well onto the MXU:

    y_intra = ((C B^T) * L) (dt x)      [chunk x chunk systolic matmul]
    y_inter = (C  state_in) * decay
    state_out = state_in * full_decay + (decayed dt x)^T B

Block shapes: chunk Q x head_dim P and chunk Q x d_state N tiles; Q, P, N
chosen as multiples of the 128-lane register tiling where the model allows.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dtc_ref, dtr_ref, b_ref, c_ref, init_ref,
                y_ref, final_ref, state_scr, *, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    A = a_ref[hi]                                    # scalar decay rate (<0)
    dt_col = dtc_ref[0, 0]                           # (Q, 1)
    dA_col = dt_col * A                              # (Q, 1)
    dA_row = dtr_ref[0, 0] * A                       # (1, Q)
    B = b_ref[0].astype(jnp.float32)                 # (Q, N)
    C = c_ref[0].astype(jnp.float32)                 # (Q, N)

    # Within-chunk cumulative decay, as a column and as a row: masked
    # reductions over the (Q, Q) causal triangle, no relayout of a vector.
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iota_i >= iota_j
    cum_col = jnp.sum(jnp.where(causal, dA_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(iota_i <= iota_j, dA_col, 0.0), axis=0,
                      keepdims=True)
    total = jnp.sum(dA_row, axis=1, keepdims=True)   # (1, 1)

    # ---- intra-chunk (dual/quadratic) term --------------------------------
    L = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))  # (Q, Q)
    dtx = x * dt_col                                              # (Q, P)
    y_intra = jax.lax.dot_general(scores * L, dtx, (((1,), (0,)), ((), ())))

    # ---- inter-chunk term ---------------------------------------------------
    state_in = state_scr[...]                                     # (P, N)
    y_inter = jax.lax.dot_general(C * jnp.exp(cum_col), state_in,
                                  (((1,), (1,)), ((), ())))       # (Q, P)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # ---- state update ---------------------------------------------------------
    decayed = dtx * jnp.exp(total - cum_col)                      # (Q, P)
    contrib = jax.lax.dot_general(decayed.T, B,
                                  (((1,), (0,)), ((), ())))       # (P, N)
    state_scr[...] = state_in * jnp.exp(total) + contrib

    @pl.when(ci == nc - 1)
    def _final():
        final_ref[0, 0] = state_scr[...].astype(final_ref.dtype)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 256,
             initial_state: Optional[jnp.ndarray] = None,
             interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (b, s, h, p); dt: (b, s, h); A: (h,); B, C: (b, s, n).

    Returns (y (b, s, h, p), final_state (b, h, p, n)).

    The kernel works head-major: x and y move as (b, h, s, p) and dt as a
    (b, h, s, 1) column plus a (b, h, 1, s) row, so that every block's
    last two dimensions are (chunk, full) tiles.  A (1, chunk, 1, p) block
    of the (b, s, h, p) array is refused by the TPU lowering, whose tiles
    need the second-to-last block dimension to be a multiple of 8.  A sits
    whole in SMEM and is read per head.
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, f"seq {s} % chunk {chunk} != 0"
    nc = s // chunk
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)
    xh = jnp.transpose(x, (0, 2, 1, 3))                      # (b, h, s, p)
    dth = jnp.transpose(dt, (0, 2, 1)).astype(jnp.float32)   # (b, h, s)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, final = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), xh, dth[..., None], dth[:, :, None, :], B, C,
      initial_state)
    return jnp.transpose(y, (0, 2, 1, 3)), final
