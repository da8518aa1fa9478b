"""Published peaks of the chips this benchmark may run on, keyed by jax's
``device_kind``. One table: every roofline share and every ``mfu`` divides
by a row of it. A device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
(Copied from ``bench.py``'s ``HBM_BYTES_PER_SEC`` table; that original is
listed under Open questions in PERF.md for a later PR to delete.)
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"chipbench: device kind {device_kind!r} is not in the peaks "
            f"table ({sorted(PEAKS)}); add its published peaks with their "
            "source before measuring on it")
    return PEAKS[device_kind]
