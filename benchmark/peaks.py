"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it: the
denominators of every MFU and roofline figure (a copy of ``bench.PEAKS``).

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 819 GB/s
of HBM bandwidth, 16 GB of HBM a chip. A kind that is not here is an error,
not a default.
"""

PEAKS = {"TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def peaks(device) -> dict:
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise SystemExit(f"no peaks recorded for device kind {device.device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
