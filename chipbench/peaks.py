"""Published peaks of one chip, keyed by ``device_kind`` substring — the one
table every utilisation and roofline figure of the benchmark divides by.

Source: Google Cloud TPU documentation, the system-architecture page of each
generation ("TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).  Copied from
``bench.py::NOMINAL_SPECS`` so that a later change to the program cannot move
the yardstick.  A device kind that is not here is an error, never a default.
"""

# device_kind substring -> (bf16 dense peak TFLOP/s, HBM GB/s)
PEAKS = {
    "v6 lite": (918.0, 1640.0), "v6e": (918.0, 1640.0),
    "v5 lite": (197.0, 819.0), "v5e": (197.0, 819.0),
    "v5p": (459.0, 2765.0),
    "v4": (275.0, 1228.0),
    "v3": (123.0, 900.0),
    "v2": (46.0, 700.0),
}


def peaks_for(device_kind: str):
    """``(peak FLOP/s, peak bytes/s)`` for this chip; longest key wins."""
    kind = device_kind.lower()
    for key in sorted(PEAKS, key=len, reverse=True):
        if key in kind:
            tflops, gbps = PEAKS[key]
            return tflops * 1e12, gbps * 1e9
    raise SystemExit(
        f"chipbench: device_kind {device_kind!r} is not in chipbench/peaks.py"
        " — add its published peak, with its source, before measuring on it")
