"""The knee sweep offers one rate to the serving driver on the CPU at a tiny
size and reports it as a line of the sweep."""
from bench import run as RUN
from bench import sweep
from bench.tests.rehearse import CpuContext


def test_sweep_offers_one_rate():
    ctx = CpuContext("dcgan.serve.poisson", 2**31 + 303, 1.0, params={"batch": 8})
    drv = RUN.load_module("drivers", "serve")
    _, engine, server = drv.build(ctx)
    try:
        drv.warm(ctx, engine, server)
        line = sweep.offer(drv, ctx, server, 20.0, 1.0)
    finally:
        server.stop(drain=True)
    assert line["rate_rps"] == 20.0 and line["offered"] == 20
    assert line["delivered"] == 20
    assert 0 < line["p50_ms"] <= line["p95_ms"] < float("inf")
    assert line["latency_growth"] > 0 and line["compiles"] >= 0
