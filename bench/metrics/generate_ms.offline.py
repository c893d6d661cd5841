"""Mean time of a serve dispatch's generator call up to its images being
ready: the ``gan.serve.generate`` span of ``GanServeEngine._serve_arch``, ms."""
from bench.metrics._program import span_ms


def read(d: dict):
    return span_ms("gan.serve.generate")
