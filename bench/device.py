"""Device facts the harness reads from JAX."""
from __future__ import annotations

import jax


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
