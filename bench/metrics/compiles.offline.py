"""Programs compiled inside the traced window of a serving cell, by the
innermost ``repro.obs`` span they compiled under."""
from bench.metrics._program import compiles


def read(d: dict):
    return compiles("gan.serve.")
