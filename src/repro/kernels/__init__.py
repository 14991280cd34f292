"""Pallas kernels: flash attention, Mamba2 SSD scan, int8 block quantizer
and the engine's fused sim step."""
import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU backend
    only.  Every other backend compiles them, and a kernel that its
    compiler refuses fails loudly rather than falling back."""
    return jax.default_backend() == "cpu"
