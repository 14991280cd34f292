"""Model FLOP/s utilization of the whole step: model FLOPs per token
(``train_flops_per_token`` of ``bench/models/<model_type>.py``) x tokens
per second over the window / chips x the chip's bf16 peak, in percent."""
from bench import registry


def read(ctx):
    if not ctx.get("tokens") or not ctx.get("window_s"):
        return None
    cfg = ctx["config"]
    rate = ctx["tokens"] / ctx["window_s"]
    chips = max(ctx["trace"].n_devices, 1)
    return 100.0 * registry.model(cfg["model_type"]).train_flops_per_token(
        cfg) * rate / (chips * ctx["peaks"]["bf16_flops_per_s"])
