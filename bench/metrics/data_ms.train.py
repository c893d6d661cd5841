"""Mean host time of the train loop's batch generation a step: the
``gan.train.data`` span of ``train_gan``, ms."""
from bench.metrics._program import span_ms


def read(d: dict):
    return span_ms("gan.train.data")
