"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``, and the least work of the kernels measured against them.

A device kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
# 16 GB of HBM at 819 GB/s
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12,
                    "hbm_bytes": 16e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def pull_iteration_bytes(arcs: int, n: int) -> int:
    """Least HBM bytes of one PageRank pull iteration over ``arcs``
    directed arcs and ``n`` vertices: per arc, its 4-byte neighbour index
    and the 4-byte value gathered through it; per vertex, its row
    pointer, degree, rank in and rank out, 4 bytes each.  No padding, no
    second pass: any implementation moves at least this much."""
    return 8 * int(arcs) + 16 * int(n)
