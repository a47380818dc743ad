"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, never a default.
"""

from __future__ import annotations

SOURCE = ("Google Cloud TPU documentation, 'TPU v5e' system architecture: "
          "per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at "
          "819 GB/s")

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class UnknownDevice(RuntimeError):
    pass


def lookup(platform: str, kind: str, allow_cpu: bool = False) -> dict | None:
    """The peaks of ``kind``.  Only a TPU is measured; ``allow_cpu`` is the
    tests' rehearsal flag and returns None, so no peak-based share can be
    formed from a CPU run."""
    if platform != "tpu":
        if allow_cpu:
            return None
        raise UnknownDevice(f"platform {platform!r} is not a TPU; the "
                            f"benchmark measures the chip only")
    if kind not in PEAKS:
        raise UnknownDevice(f"device_kind {kind!r} is not in peaks.py "
                            f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]
