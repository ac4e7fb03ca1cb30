"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
JAX reports a v5e chip's kind as "TPU v5 lite".
"""

from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e"'

PEAKS = {"TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}}


def peaks_for(device_kind: str) -> dict:
    """The peak table of ``device_kind``; an unknown kind is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add them with their source") from None


def roofline_share(flops: float, bytes_moved: float, seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """Least time the chip could take (the larger of the compute bound
    at the bf16 peak and the bandwidth bound at the HBM peak) over the
    measured time, in percent, and which bound it is."""
    t_compute = flops / peaks["bf16_flops"]
    t_memory = bytes_moved / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
