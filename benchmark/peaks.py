"""Published peaks, keyed by `device_kind` as JAX reports it. A device that
is not in the table is an error, not a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM at 819 GB/s, per chip
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12, "hbm_bytes": 16e9},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12, "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(f"no published peak {what!r} for device kind {device_kind!r}") from None


def state_bytes(state) -> int:
    """Resident bytes of the server's state planes, from their shapes."""
    import jax

    return int(sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state)))


def integrate_min_seconds(resident_bytes: int, device_kind: str) -> float:
    """The least time one integrate step can take on this chip: every state
    plane read once and written once (the step is not donated and touches
    every room slot), at the HBM peak. Bandwidth-bound: the step does no
    matrix work."""
    return 2.0 * resident_bytes / peak(device_kind, "hbm_bytes_per_s")
