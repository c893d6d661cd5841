"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json`` and
``bench/workloads/<name>.json``, its configuration in
``bench/configs/<config>.json``, its window driver in
``bench/drivers/<driver>.py`` and each per-layer metric in
``bench/metrics/<metric>.py``.  The run refuses any backend but a TPU with
at least the cell's chips, keeps JAX's compilation cache at a fixed path in
the checkout, and prints, as the last line of stdout, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``), then the numbers compared with their
limits, which also close stderr.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache"


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_workload(name: str) -> tuple[dict, dict]:
    """(the workload file ``bench/workloads/<name>.json``, its configuration)."""
    wl = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    return wl, json.loads((BENCH / "configs" / f"{wl['config']}.json").read_text())


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, its cell entry, the workload file, the configuration)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = cells[name]
    wl, cfg = load_workload(name)
    for key in ("config", "traffic", "chips"):
        if wl[key] != entry[key]:
            raise ValueError(f"{name}: {key} {wl[key]!r} in the workload file, "
                             f"{entry[key]!r} in BENCHMARK.json")
    return bench, entry, wl, cfg


def configure_jax():
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def device_info(jax, chips: int) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (backend {dev.platform!r})")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def cell_metrics(bench: dict, name: str, key: str) -> list[dict]:
    """The cell's metrics of ``key`` (end_to_end or per_layer)."""
    return [m for m in bench[key] if name in m.get("workloads", [name])]


class Context:
    """What a driver gets: the cell, its seed and window, the clock the
    set-up time is measured from, and the tracer."""

    def __init__(self, name, entry, wl, cfg, seed, seconds, trace):
        from bench.tracing import Tracer

        self.name, self.entry, self.workload, self.cfg = name, entry, wl, cfg
        self.params = wl["params"]
        self.limits = wl["limits"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_process = T_PROCESS
        self.tracer = Tracer(CACHE_DIR / "trace", enabled=trace)


def run(argv=None) -> tuple[dict, dict]:
    """Run the cell; returns its result line and the driver's notes."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, entry, wl, cfg = load_cell(args.workload)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import system

    system.import_program()
    jax = configure_jax()
    device = device_info(jax, entry["chips"])
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if device["kind"] not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {device['kind']!r} in bench/peaks.json")

    ctx = Context(args.workload, entry, wl, cfg, args.seed, args.seconds, bool(args.trace))
    out = load_module("drivers", wl["driver"]).run(ctx)
    return assemble(bench, ctx, out, device, peaks[device["kind"]]), out.get("notes", {})


def assemble(bench: dict, ctx: Context, out: dict, device: dict, peak: dict) -> dict:
    """The result line of a run from what its driver measured."""
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    metrics = {}
    if ctx.trace:
        red = out["trace"]
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        data = {"cell": ctx.name, "cfg": ctx.cfg, "workload": ctx.workload, "driver": out,
                "trace": red, "peak": peak}
        for m in cell_metrics(bench, ctx.name, "per_layer"):
            value = load_module("metrics", m["name"]).read(data)
            if isinstance(value, dict):
                metrics[m["name"]] = {**value, "unit": m["unit"]}
            elif value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, ctx.name, "end_to_end"):
            metrics[m["name"]] = {"value": out["e2e"][m["name"]], "unit": m["unit"]}
    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if ctx.trace:
        result["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                               "idle_gaps": out["trace"]["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in out["checks"].items()}
    return result


def main(argv=None) -> int:
    result, notes = run(argv)
    for k, v in notes.items():
        print(f"note {k} = {v!r}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
