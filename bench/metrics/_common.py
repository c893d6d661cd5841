"""Arithmetic shared by the per-layer readers.  Each reader gets the run's
data: ``driver`` (what the window driver measured), ``trace`` (the reduced
profiler trace of a ``--trace 1`` run), ``peak`` (the chip's row of
``bench/peaks.json``) and the cell's ``cfg`` and ``workload``."""
from __future__ import annotations

from bench import flops as F


def mfu(d: dict):
    """Model FLOPs of the window's work over the window and the bf16 peak, %."""
    w = d["driver"]["window_s"]
    return 100.0 * d["driver"]["work"]["model_flops"] / w / d["peak"]["bf16_flops_per_s"]


def engine_roofline(d: dict):
    """Least time the conv and deconv passes of the traced work could take
    (per pass the larger of the FLOP and HBM-byte bounds) over the Mosaic
    kernels' device time, %, with the bound that dominates."""
    kernel_s = d["trace"]["kernel_s"]
    if not kernel_s:
        return None
    t_min, bound = F.engine_bound(d["driver"]["work"]["engine_passes"], d["peak"])
    return {"value": 100.0 * t_min / kernel_s, "bound": bound}


def device_idle(d: dict):
    """Share of the traced window in which no operation ran on the device, %."""
    t = d["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
