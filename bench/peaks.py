"""The chip peaks the benchmark divides by, keyed by ``device_kind``.

A device that is not in ``peaks.json`` is an error: a share of a peak
that nobody published is not a number.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def peaks(device_kind: str, table: Path = PEAKS_FILE) -> dict:
    rows = json.loads(Path(table).read_text())
    if device_kind not in rows:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; known: {sorted(rows)}")
    return rows[device_kind]


def roofline_seconds(flops: float, bytes_moved: float, peak: dict):
    """The least time the chip could take, and which term bounds it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_moved / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
