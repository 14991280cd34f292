"""Fused Pallas sim-step kernel for the batched checkpoint-policy engine.

The engine's JAX backend normally advances a cell batch with a jitted
``lax.scan`` over ``chunk`` steps (:func:`repro.sim.engine._jax_chunk`):
every step streams the whole carried state through HBM, and the scan has
no way to stop early — a chunk whose cells all finish on step 3 still
executes the remaining ``chunk - 3`` no-op steps.

This kernel fuses the branchless ``_attempt`` -> ``_replica_draw`` ->
``_apply`` inner step into one :func:`pl.pallas_call`:

* the grid is 1-D over blocks of ``block_b`` cells; each program keeps its
  block's entire carried state in VMEM (registers/interpreter values on
  CPU) across the whole chunk — one HBM round-trip per chunk instead of
  one per step;
* the step loop is a ``lax.while_loop`` that exits as soon as every cell
  in the block is finished.  Post-finish steps are state no-ops by
  construction (``active = ~finished`` masks every update), so skipping
  them is bit-identical — this is the early exit the scan body cannot
  express, and where the speedup on completion-skewed batches comes from;
* the per-step noise is pre-generated by :func:`gen_draws`, which runs the
  exact key-split chain of the scan body — same splits, same order, same
  dtypes — so the fused and scan paths are bit-identical on supported
  batches (no per-peer-form cells: ``peer_axis == 1``, i.e. pooled and
  class-pooled estimators only).

On CPU (the CI and development platform) the kernel runs in Pallas
interpret mode.  Mosaic does not compile it for a TPU yet (see
:data:`TPU_REFUSAL`), so on any other backend :func:`fused_chunk` raises
instead of interpreting.  Benchmarked head-to-head with the scan body in
benchmarks/kernel_bench.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from repro.sim import engine as _eng

_DEFAULT_BLOCK_B = 64

#: Why Mosaic refuses this kernel (compile for a described TPU v5e, JAX 0.9).
TPU_REFUSAL = (
    "step='fused' does not compile for a TPU: Mosaic refuses its rank-1 "
    "blocks of block_b cells (they do not tile as (8, 128); the lowering's "
    "block check fails on the 64-bit operands) and its float64/int64 state "
    "operands (X64 element types are not lowered).  It needs float32 state "
    "and (8, 128) tiles first; run step='scan' on this backend.")


def gen_draws(keys, chunk: int, any_pm: bool):
    """Pre-generate ``chunk`` steps of per-cell noise draws.

    Replays :func:`repro.sim.engine._step_draws` (the scan body's key
    chain) for ``chunk`` steps and stacks the results on a leading step
    axis.  Returns ``(keys, (u, z, u2, u_pm, z_pm))`` where the advanced
    keys equal what the scan body would carry out of the chunk, so the
    host loop can keep alternating between the two paths mid-run.
    """
    def body(keys, _):
        keys, u, z, u2, _, _, u_pm, z_pm = _eng._step_draws(keys, 1, any_pm)
        outs = (u, z, u2) + ((u_pm, z_pm) if any_pm else ())
        return keys, outs

    keys, draws = jax.lax.scan(body, keys, None, length=chunk)
    return keys, draws


def _sim_step_kernel(p_refs, s_refs, d_refs, o_refs, n_ref, *,
                     chunk: int, macro_threshold: float, any_store: bool,
                     any_het: bool, any_shock: bool, any_pm: bool):
    p = _eng._Params(*(r[...] for r in p_refs))
    s0 = _eng._State(*(r[...] for r in s_refs))
    draws = tuple(r[...] for r in d_refs)
    u_a, z_a, u2_a = draws[:3]
    upm_a, zpm_a = draws[3:] if any_pm else (None, None)

    def cond(carry):
        t, s = carry
        return (t < chunk) & jnp.any(~s.finished)

    def body(carry):
        t, s = carry
        u_pm = upm_a[t] if any_pm else None
        z_pm = zpm_a[t] if any_pm else None
        pre = _eng._attempt(s, p, u2_a[t], jnp, _eng.lambertw0_jnp,
                            any_store, any_het, any_shock)
        s = _eng._apply(s, p, pre, u_a[t], z_a[t], None, None, u_pm, z_pm,
                        macro_threshold, 1, any_pm, jnp)
        return t + 1, s

    t_end, s = jax.lax.while_loop(cond, body, (jnp.int32(0), s0))
    for r, v in zip(o_refs, s):
        r[...] = v.astype(r.dtype)
    n_ref[...] = jnp.full((1,), t_end, jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "macro_threshold", "any_store", "any_het", "any_shock", "any_pm",
    "chunk", "block_b", "interpret"))
def _fused_call(s, keys, p, *, macro_threshold, any_store, any_het,
                any_shock, any_pm, chunk, block_b, interpret):
    keys, draws = gen_draws(keys, chunk, any_pm)
    B = s.t.shape[0]
    grid = (B // block_b,)

    blk = lambda x: pl.BlockSpec(
        (block_b,) + x.shape[1:],
        lambda i, nd=np.ndim(x): (i,) + (0,) * (nd - 1))
    dblk = lambda x: pl.BlockSpec(
        (chunk, block_b) + x.shape[2:],
        lambda i, nd=np.ndim(x): (0, i) + (0,) * (nd - 2))
    kernel = functools.partial(
        _sim_step_kernel, chunk=chunk, macro_threshold=macro_threshold,
        any_store=any_store, any_het=any_het, any_shock=any_shock,
        any_pm=any_pm)
    s, n_steps = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=(jax.tree.map(blk, p), jax.tree.map(blk, s),
                  jax.tree.map(dblk, draws)),
        out_specs=(jax.tree.map(blk, s),
                   pl.BlockSpec((1,), lambda i: (i,))),
        out_shape=(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s),
            jax.ShapeDtypeStruct(grid, jnp.int32)),
        interpret=interpret,
    )(p, s, draws)
    return s, keys, n_steps


def fused_chunk(s, keys, p, *, macro_threshold: float, any_store: bool,
                any_het: bool, any_shock: bool, any_pm: bool, chunk: int,
                block_b: int = _DEFAULT_BLOCK_B, interpret=None):
    """Advance the batch by (up to) ``chunk`` steps through the kernel.

    Drop-in replacement for one ``_jax_chunk`` call on supported batches
    (``peer_axis == 1``); bit-identical by construction.  ``block_b``
    falls back to one whole-batch block when it does not divide B (the
    padding-free default); ``interpret=None`` defers to
    :func:`repro.kernels.interpret_mode`.  Compiling the kernel raises
    ``NotImplementedError`` with :data:`TPU_REFUSAL`.
    """
    if interpret is None:
        interpret = interpret_mode()
    if not interpret:
        raise NotImplementedError(TPU_REFUSAL)
    B = s.t.shape[0]
    if B % block_b != 0:
        block_b = B
    s, keys, _ = _fused_call(
        s, keys, p, macro_threshold=macro_threshold, any_store=any_store,
        any_het=any_het, any_shock=any_shock, any_pm=any_pm, chunk=chunk,
        block_b=block_b, interpret=bool(interpret))
    return s, keys
