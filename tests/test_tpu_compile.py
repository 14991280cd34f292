"""Compiles for a described TPU v5e: what the chip's compiler would refuse.

Nothing here runs on a chip.  Each test lowers a program of the main path
at real widths and compiles it with the TPU compiler for one chip of a
described (not attached) v5e, which catches what interpret mode and the
CPU backend cannot: Mosaic block/layout refusals, 64-bit operands in a
kernel, and programs that do not fit the chip's HBM.  The topology is
described inside a fixture, so importing this file never loads libtpu.
"""
import dataclasses
import importlib
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

HBM_V5E = 15.75e9  # bytes the v5e compiler lets one program use


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep it from being written there."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _repo_module(name):
    """A module from the repository root (chip_smoke, benchmarks.*)."""
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    try:
        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _fleet_batch(one_chip, B):
    """Packed params, initial state and keys of chip_smoke's engine grid."""
    from repro.sim import engine as E

    cells = _repo_module("benchmarks.fleet").million_peer_cells(B)
    p = E._Params(*(jnp.asarray(a) for a in E._pack(cells)))
    s = jax.eval_shape(lambda q: E._init_state(q, jnp, 1), p)
    keys = jax.ShapeDtypeStruct((B, 2), jnp.uint32)
    return _on(one_chip, s), _on(one_chip, keys), _on(one_chip, p)


def test_engine_scan_chunk_compiles(one_chip):
    """The engine's lax.scan chunk (f64/i64 state, emulated by XLA) at
    chip_smoke's 10,000-cell 1M-peer grid."""
    from repro.sim import engine as E

    with jax.enable_x64(True):
        s, keys, p = _fleet_batch(one_chip, 10_000)
        chunk = jax.jit(E._jax_chunk, static_argnums=(2, 3, 4, 5, 6, 7, 8))
        compiled = chunk.lower((s, keys), p, 0.05, False, False, False, True,
                               1, E.DEFAULT_CHUNK).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_fused_sim_step_is_refused(one_chip):
    """Mosaic refuses the fused kernel (rank-1 blocks, 64-bit operands),
    which is why ``fused_chunk`` raises off the CPU.  When this starts to
    compile, lift that guard."""
    from repro.kernels import sim_step

    with jax.enable_x64(True):
        s, keys, p = _fleet_batch(one_chip, 1024)
        with pytest.raises(Exception):
            sim_step._fused_call.lower(
                s, keys, p, macro_threshold=0.05, any_store=False,
                any_het=False, any_shock=False, any_pm=True, chunk=256,
                block_b=64, interpret=False).compile()


def test_fused_step_raises_when_compiled(monkeypatch):
    """Off the CPU interpreter, step='fused' names the compiler's refusal
    instead of interpreting or falling back."""
    from repro.kernels import sim_step
    from repro.sim import CellSpec, PolicyConfig, run_cells, scenario

    monkeypatch.setattr(sim_step, "interpret_mode", lambda: False)
    cells = [CellSpec(scenario=scenario("constant", mtbf=4000.0),
                      policy=PolicyConfig(kind="fixed", fixed_T=900.0),
                      seed=s, k=4, work=3600.0) for s in range(2)]
    with pytest.raises(NotImplementedError, match="Mosaic refuses"):
        run_cells(cells, backend="jax", step="fused", mesh=None)


def test_olmo1b_train_step_fits_one_chip(one_chip, tmp_path):
    """chip_smoke's trainer step (olmo-1b, 8 of 16 layers, seq 2048,
    batch 4) through FaultTolerantTrainer's own jit: the donated state
    aliases the output, and argument + temp fit the chip."""
    from repro.ckpt import AsyncCheckpointer
    from repro.configs import get_config
    from repro.data import DataConfig
    from repro.runtime import FaultTolerantTrainer
    from repro.train.step import init_train_state

    chip_smoke = _repo_module("chip_smoke")
    cfg = dataclasses.replace(get_config(chip_smoke.TRAIN_ARCH),
                              n_layers=chip_smoke.TRAIN_LAYERS)
    seq, batch = chip_smoke.TRAIN_SEQ, chip_smoke.TRAIN_BATCH
    ckpt = AsyncCheckpointer(str(tmp_path))
    try:
        trainer = FaultTolerantTrainer(
            cfg, DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch),
            ckpt=ckpt)
        state = _on(one_chip, jax.eval_shape(
            lambda k: init_train_state(k, cfg), jax.random.key(0)))
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
        mem = trainer.train_step.lower(
            state, {"tokens": tok, "labels": tok}).compile().memory_analysis()
    finally:
        ckpt.close()
    assert mem.alias_size_in_bytes >= 0.99 * mem.argument_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_V5E


def _kernel_cases():
    """(name, fn, shapes) of every Pallas kernel that compiles, at the
    widths of the model that uses it."""
    from repro.kernels import ckpt_quant, flash_attention, ssd_scan

    n = 2048 * 8192  # one olmo-1b MLP matrix, quantized for a checkpoint
    bg, s, d = 4 * 16, 2048, 128  # olmo-1b: batch 4 x 16 heads, seq 2048
    b, sm, h, p, st = 4, 2048, 24, 64, 128  # mamba2-130m
    bf16, f32 = jnp.bfloat16, jnp.float32
    return {
        "flash_attention": (
            lambda q, k, v: flash_attention.flash_attention(
                q, k, v, scale=d ** -0.5),
            [((bg, 1, s, d), bf16), ((bg, s, d), bf16), ((bg, s, d), bf16)]),
        "ssd_scan": (
            lambda x, dt, a, bb, c: ssd_scan.ssd_scan(x, dt, a, bb, c,
                                                      chunk=256),
            [((b, sm, h, p), bf16), ((b, sm, h), f32), ((h,), f32),
             ((b, sm, st), bf16), ((b, sm, st), bf16)]),
        "quantize_blocks": (
            lambda x: ckpt_quant.quantize_blocks(x, 512),
            [((n,), f32)]),
        "dequantize_blocks": (
            lambda q, sc: ckpt_quant.dequantize_blocks(q, sc, 512),
            [((n,), jnp.int8), ((n // 512,), f32)]),
    }


@pytest.mark.parametrize("name", ["flash_attention", "ssd_scan",
                                  "quantize_blocks", "dequantize_blocks"])
def test_kernel_compiles_at_real_widths(name, one_chip):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert np.isfinite(compiled.memory_analysis().argument_size_in_bytes)
