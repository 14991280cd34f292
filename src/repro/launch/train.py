"""Training entry point (single-host execution of the production stack).

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 20 \
        --smoke --ckpt-dir /tmp/ckpt --mtbf 3600

Runs the fault-tolerant trainer: real train steps, adaptive checkpointing
(the paper's controller), virtual-clock failure injection, restart from the
sharded checkpoint store.  ``--smoke`` selects the reduced config (CPU).
Without it the full architecture is built, which for olmo-1b does not fit
one 16 GB TPU v5e: at all 16 layers the compiler asks for 20.4 GB of HBM
(bf16 params plus f32 AdamW state and activations).  ``chip_smoke.py``
trains it at 8 of its 16 layers, at seq 2048 and batch 4.
"""
from __future__ import annotations

import argparse

from repro.ckpt import AsyncCheckpointer
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import CheckpointPolicyConfig, FailureInjector, FaultTolerantTrainer
from repro.sim.network import constant_mtbf


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--replicas", type=int, default=1,
                    help="neighbour checkpoint replicas")
    ap.add_argument("--policy", choices=["adaptive", "fixed"], default="adaptive")
    ap.add_argument("--fixed-interval", type=float, default=600.0)
    ap.add_argument("--mtbf", type=float, default=4 * 3600.0,
                    help="per-node MTBF (virtual seconds)")
    ap.add_argument("--nodes", type=int, default=64)
    ap.add_argument("--step-seconds", type=float, default=20.0,
                    help="virtual seconds per step for the churn clock")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch)
    ckpt = AsyncCheckpointer(
        args.ckpt_dir,
        replicas=[f"{args.ckpt_dir}_rep{i}" for i in range(args.replicas)],
        n_shards=4)
    injector = FailureInjector(k=args.nodes, mtbf_fn=constant_mtbf(args.mtbf),
                               seconds_per_step=args.step_seconds)
    trainer = FaultTolerantTrainer(
        cfg, data_cfg, ckpt=ckpt, injector=injector,
        policy=CheckpointPolicyConfig(kind=args.policy,
                                      fixed_interval=args.fixed_interval,
                                      prior_mtbf=args.mtbf),
        n_microbatches=args.microbatches)
    report = trainer.run(n_steps=args.steps)
    print(f"steps={report.steps_completed} virtual_hours="
          f"{report.virtual_time / 3600:.2f} failures={report.n_failures} "
          f"checkpoints={report.n_checkpoints} restarts={report.n_restarts} "
          f"final_loss={report.losses[-1] if report.losses else float('nan'):.4f} "
          f"interval*={report.controller_interval:.0f}s")
    ckpt.close()


if __name__ == "__main__":
    main()
