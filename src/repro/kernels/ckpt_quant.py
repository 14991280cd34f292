"""Pallas TPU kernel: per-block symmetric int8 quantization.

This attacks the paper's checkpoint-overhead term V directly: Sec 3.1.2
names '(ii) compressing the checkpointed status costs some processing
cycles (iii) available bandwidth ... to upload the checkpoint image'.
Block-quantizing the state to int8 (+ one fp32 scale per block) cuts the
upload 4x (bf16) to 8x (fp32 master) for a cheap on-accelerator pass —
shrinking both V and T_d, which the utilization model then converts into a
LONGER optimal interval (fewer checkpoints, higher U).  The same kernel
pair implements int8 gradient compression with error feedback
(train/compress.py).

Tiling: the flat input is viewed as (n_blocks, block); each grid step
stages one (block_rows x block) tile into VMEM, computes row-wise absmax
scales on the VPU, and writes int8 codes + fp32 scales.  The scales
travel as an (n_blocks, 1) column, blocked (block_rows, 1): a rank-1
(block_rows,) block of a 1-D f32 array is refused by Mosaic, whose
(256)-tiling of it does not match XLA's (1024)-tiling of the operand.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                  # (rows, block)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)   # (rows, 1)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    x_ref[...] = (q * s_ref[...]).astype(x_ref.dtype)


def quantize_blocks(x: jnp.ndarray, block: int = 512, *,
                    block_rows: int = 256,
                    interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: flat (N,) with N % block == 0 -> (codes int8 (N,), scales f32 (N/block,))."""
    assert x.ndim == 1 and x.shape[0] % block == 0, (x.shape, block)
    n_blocks = x.shape[0] // block
    block_rows = min(block_rows, n_blocks)
    assert n_blocks % block_rows == 0, (n_blocks, block_rows)
    xb = x.reshape(n_blocks, block)

    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(n_blocks // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, block), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, block), jnp.int8),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb)
    return q.reshape(-1), s.reshape(-1)


def dequantize_blocks(q: jnp.ndarray, scales: jnp.ndarray, block: int = 512, *,
                      block_rows: int = 256, dtype=jnp.float32,
                      interpret: bool = False) -> jnp.ndarray:
    assert q.ndim == 1 and q.shape[0] % block == 0
    n_blocks = q.shape[0] // block
    block_rows = min(block_rows, n_blocks)
    assert n_blocks % block_rows == 0
    qb = q.reshape(n_blocks, block)

    x = pl.pallas_call(
        _dequant_kernel,
        grid=(n_blocks // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, block), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, block), dtype),
        interpret=interpret,
    )(qb, scales.reshape(n_blocks, 1))
    return x.reshape(-1)
