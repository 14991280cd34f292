"""The chips a run measures, and their published peaks.

A run measures TPU chips only.  ``require_chips`` refuses any other
platform, fewer chips than the cell asks for, and a chip whose
``device_kind`` the peaks table (``bench/peaks.json``) does not list.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """No TPU, too few chips, or a chip the peaks table does not know."""


@dataclass(frozen=True)
class Chips:
    platform: str
    kind: str
    count: int
    peaks: Dict[str, Any]
    devices: List[Any]

    def memory_peak_bytes(self) -> int:
        """Peak bytes in use on the fullest chip, as the runtime reports."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks, default=0))


def load_peaks(path: Path = PEAKS_FILE) -> Dict[str, Dict[str, Any]]:
    with open(path) as f:
        return json.load(f)


def peaks_for(kind: str, table: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in {PEAKS_FILE.name} "
                     f"(have {sorted(table)})")
    return table[kind]


def require_chips(n: int) -> Chips:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"no TPU: JAX found {platform}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips and JAX sees {len(devices)}")
    kind = devices[0].device_kind
    return Chips(platform=platform, kind=kind, count=len(devices),
                 peaks=peaks_for(kind, load_peaks()), devices=list(devices))
