"""Pallas TPU flash attention (forward).

TPU-native blocking (DESIGN.md: HBM->VMEM->MXU):
    * grid = (B*G, R, n_q_blocks, n_kv_blocks); the kv dimension is the
      innermost, SEQUENTIAL grid axis — TPU grids execute in order, so the
      online-softmax running statistics (m, l, acc) live in VMEM scratch
      and carry across kv steps;
    * q blocks (block_q x D) and kv blocks (block_kv x D) are staged into
      VMEM by BlockSpec; D and the block sizes are multiples of 128 to keep
      the MXU systolic array full;
    * fp32 accumulation; bf16 inputs; output cast back to the input dtype;
    * causal masking is bottom-right aligned (decode windows) computed from
      global positions; fully-masked kv blocks short-circuit via pl.when.

GQA layout: the caller folds kv groups into the leading axis —
q (B*G, R, Sq, D), k/v (B*G, Skv, D) — so each grid row reads one kv head
and R query heads, which is exactly the VMEM reuse GQA exists to provide.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, softcap: Optional[float],
                  block_q: int, block_kv: int, sq: int, skv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q + (skv - sq)   # bottom-right aligned global q pos
    k_start = ki * block_kv

    # Skip kv blocks strictly above the causal diagonal.
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (bq, D)
        k = k_ref[0].astype(jnp.float32)                # (bkv, D)
        v = v_ref[0].astype(jnp.float32)                # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_scr[...]                             # (bq, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (bq, bkv)
        correction = jnp.exp(m_prev - m_new)
        l_new = correction * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    scale: float, causal: bool = True,
                    softcap: Optional[float] = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (BG, R, Sq, D); k, v: (BG, Skv, D) -> (BG, R, Sq, D)."""
    BG, R, Sq, D = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, block_q, Skv, block_kv)
    grid = (BG, R, Sq // block_q, Skv // block_kv)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, softcap=softcap,
        block_q=block_q, block_kv=block_kv, sq=Sq, skv=Skv)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, r, qi, ki: (b, r, qi, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, r, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, r, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, r, qi, ki: (b, r, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
