"""Jitted public wrappers over the Pallas kernels.

``interpret=None`` defers to :func:`repro.kernels.interpret_mode`: on the
CPU backend the kernel body runs as a traced python function (the same
control flow as the TPU lowering); on a TPU it is compiled by Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ckpt_quant as _q
from repro.kernels import flash_attention as _fa
from repro.kernels import interpret_mode
from repro.kernels import ssd_scan as _ssd


@functools.partial(jax.jit, static_argnames=("scale", "causal", "softcap",
                                             "block_q", "block_kv", "interpret"))
def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    softcap: Optional[float] = None, block_q: int = 128,
                    block_kv: int = 128, interpret: Optional[bool] = None):
    """GQA flash attention: q (BG, R, Sq, D), k/v (BG, Skv, D)."""
    interpret = interpret_mode() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, scale=scale, causal=causal,
                               softcap=softcap, block_q=block_q,
                               block_kv=block_kv, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, initial_state=None,
             interpret: Optional[bool] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba2 SSD: x (b,s,h,p), dt (b,s,h), A (h,), B/C (b,s,n)."""
    interpret = interpret_mode() if interpret is None else interpret
    return _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk,
                         initial_state=initial_state, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "block_rows", "interpret"))
def quantize_blocks(x, *, block: int = 512, block_rows: int = 256,
                    interpret: Optional[bool] = None):
    interpret = interpret_mode() if interpret is None else interpret
    return _q.quantize_blocks(x, block, block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "block_rows", "dtype", "interpret"))
def dequantize_blocks(q, scales, *, block: int = 512, block_rows: int = 256,
                      dtype=jnp.float32, interpret: Optional[bool] = None):
    interpret = interpret_mode() if interpret is None else interpret
    return _q.dequantize_blocks(q, scales, block, block_rows=block_rows,
                                dtype=dtype, interpret=interpret)
