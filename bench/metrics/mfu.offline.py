"""Model FLOP utilization of the window: model FLOPs over time and the
bf16 peak (``_common.mfu``)."""
from bench.metrics._common import mfu as read  # noqa: F401
