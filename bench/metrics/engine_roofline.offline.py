"""Engines' share of their roofline: the conv and deconv layers' direct
work over the Mosaic kernels' device time (``_common.engine_roofline``)."""
from bench.metrics._common import engine_roofline as read  # noqa: F401
