"""Find the highest rate a serve cell's configuration sustains.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> --rates r1,r2,...

The workload file need not have a cell in ``BENCHMARK.json``.  One process
builds and warms the cell's server once, then offers each rate
open-loop for ``seconds`` (the cell's traffic with its rate replaced) and
prints one JSON line per rate: requests offered and delivered within the
window plus 1 s, the p50/p95 latency from the due time, and the median
latency of the last fifth of the requests over the first fifth, which
grows with the backlog, and the programs compiled while the rate ran.  The knee is the highest rate that delivers at
least 0.98 of what it offered with that ratio under 2; a cell is set to
about 0.8 of it.  No correctness check runs here.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run as RUN  # noqa: E402


def offer(drv, ctx, server, rate: float, seconds: float) -> dict:
    """Offer one rate open-loop for ``seconds``; the sweep's line for it."""
    from bench import traffic as TR

    ctx.params = dict(ctx.workload["params"], rate_rps=rate)
    compiled = drv.compiles()
    t0 = time.perf_counter()
    w = drv.open_loop(ctx, server, t0)
    compiled = drv.compiles() - compiled
    sent = w.sent
    lat = TR.latencies_ms([s.due for s in sent], [s.done for s in sent])
    n = len(sent)
    on_time = sum(1 for s in sent if s.done is not None and s.done <= t0 + seconds + 1)
    fifth = max(1, n // 5)
    growth = statistics.median(lat[-fifth:]) / statistics.median(lat[:fifth])
    return {"rate_rps": rate, "offered": n, "delivered": on_time,
            "p50_ms": TR.percentile(lat, 50), "p95_ms": TR.percentile(lat, 95),
            "latency_growth": growth, "compiles": compiled,
            "send_lag_p95_ms": TR.percentile([(s.sent - s.due) * 1e3 for s in sent], 95)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    wl, cfg = RUN.load_workload(args.workload)
    from bench import system

    system.import_program()
    jax = RUN.configure_jax()
    RUN.device_info(jax, wl["chips"])
    drv = RUN.load_module("drivers", wl["driver"])
    ctx = RUN.Context(args.workload, wl, wl, cfg, args.seed, args.seconds, False)
    _, engine, server = drv.build(ctx)
    drv.warm(ctx, engine, server)
    for rate in (float(r) for r in args.rates.split(",")):
        print(json.dumps(offer(drv, ctx, server, rate, args.seconds)), flush=True)
    server.stop(drain=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
