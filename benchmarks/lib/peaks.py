"""Published peaks of a chip, keyed by ``device_kind``, and a work's share of
them. A device that is not in the table is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
    # 16 GB of HBM a chip
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(f"no published peaks for device kind {device_kind!r}: "
                         "add it to benchmarks/lib/peaks.py with its source")
    return PEAKS[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> float:
    """The least time the chip could take for the work over the time it took,
    in percent."""
    least = max(flops / peaks["flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
