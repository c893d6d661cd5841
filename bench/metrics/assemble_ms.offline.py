"""Mean host time of a serve dispatch's assembly (the requests' latents
concatenated, copied to the device and padded to the bucket): the
``gan.serve.assemble`` span of ``GanServeEngine._serve_arch``, ms."""
from bench.metrics._program import span_ms


def read(d: dict):
    return span_ms("gan.serve.assemble")
