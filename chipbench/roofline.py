"""Peaks of the chip and the least time of a packed linear.

The peak table is keyed by JAX's ``device_kind``; a device missing from
it is an error, never a default.  Source of the v5e row: Google Cloud
documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s).

The work is the algorithm's, from a linear's shape and each call's row
count ``M`` as the engine shapes it: never from a kernel's blocking,
padding or even/odd split, so a kernel written anew is read against the
same work.  Which linears a model has, and its FLOPs per position, are
its family module's (``families/<family>.py``).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"device kind {device_kind!r} is not in the peak "
                       f"table ({sorted(PEAKS)})")
    return PEAKS[device_kind]


def int4_linear_cost(m: int, k: int, n: int) -> tuple[int, int]:
    """(operations, bytes) of one int8-activation x int4-weight linear:
    2·M·K·N operations; K·N/2 bytes of nibbles, 4·N of f32 scales, M·K of
    int8 activations in and 4·M·N of int32 results out."""
    return 2 * m * k * n, k * n // 2 + 4 * n + m * k + 4 * m * n


def min_time_s(ops: float, nbytes: float, peak_ops: float,
               bytes_per_s: float) -> float:
    """The least time the chip could take: the larger of its two bounds."""
    return max(ops / peak_ops, nbytes / bytes_per_s)
