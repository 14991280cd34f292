"""Model FLOP/s utilization of the whole step: model FLOPs per token
(``bench/flops.py``) x tokens per second over the window / chips x the
chip's bf16 peak, in percent."""
from bench.flops import train_flops_per_token


def read(ctx):
    if not ctx.get("tokens") or not ctx.get("window_s"):
        return None
    rate = ctx["tokens"] / ctx["window_s"]
    chips = max(ctx["trace"].n_devices, 1)
    return 100.0 * train_flops_per_token(ctx["config"]) * rate / (
        chips * ctx["peaks"]["bf16_flops_per_s"])
