"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device not in the table is an error."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 16 GB of HBM2 at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8.
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str, what: str) -> float:
    """One peak of ``device_kind``; raises for a device not in the table."""
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(f"no {what!r} peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
