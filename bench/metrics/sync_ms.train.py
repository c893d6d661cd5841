"""Mean host time of the train loop's metrics fetch a step, where it
waits for the device: the ``gan.train.sync`` span of ``train_gan``, ms."""
from bench.metrics._program import span_ms


def read(d: dict):
    return span_ms("gan.train.sync")
