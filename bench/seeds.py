"""Seeds derived from ``--seed``, which may be any non-negative integer."""
from __future__ import annotations

import numpy as np


def derive(seed: int, tag: int, bits: int = 31) -> int:
    """A reproducible integer below ``2**bits`` for stream ``tag``."""
    word = np.random.SeedSequence([int(seed), int(tag)]).generate_state(
        1, dtype=np.uint64)[0]
    return int(word) % (1 << bits)
