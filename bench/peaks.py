"""Published peaks of each accelerator, keyed by ``device_kind`` as JAX
reports it.  A device that is not in the table is an error, never a
default: a roofline share against the wrong peak is a wrong number."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' system "
                  "architecture: per-chip peak HBM bandwidth",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises for a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/peaks.py "
                         f"with their source") from None
