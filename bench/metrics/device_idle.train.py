"""Share of the traced window with no operation on the device."""
from bench.metrics._common import device_idle as read  # noqa: F401
