"""The chip's published peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

from typing import Dict

from bench.harness import BENCH, BenchError, load_json


def peaks(device_kind: str, table: Dict = None) -> Dict[str, float]:
    """``{"bf16_flops_per_s", "hbm_bytes_per_s"}`` of one chip; a device
    missing from ``bench/peaks.json`` is an error, not a default."""
    table = load_json(BENCH / "peaks.json") if table is None else table
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json") from None
