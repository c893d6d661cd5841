"""Readings that set the limits of ``correct``; never run by the benchmark.

    python3 bench/control.py --workload <name> --seeds s1,s2,... --what <what> [--seconds s]

``--what``:

* ``program`` — the cell as the benchmark runs it, one short window per
  seed in one process: the numbers compared, per seed (the lower reading is
  the largest over a dozen seeds or more);
* ``control`` — the reference with every matmul operand in float8 put in
  the program's place, against the reference at HIGHEST (the upper reading
  is the smallest over the seeds);
* ``control_bf16`` — the same with the reference computed in bfloat16,
  which the program's own one-pass bfloat16 matmuls read like;
* ``half_batch`` (training) — the reference with half of the batch left
  out and the mean taken over the rest, against the reference.

A training state left unchanged reads 1 on ``change_gap`` by its measure
and needs no run.  Each line of output is one JSON object per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import reference as R  # noqa: E402
from bench import run as RUN  # noqa: E402


def train_reading(ctx, what: str) -> dict:
    import jax.numpy as jnp

    drv = RUN.load_module("drivers", "train")
    cfg, batch, seed = ctx.cfg, ctx.params["batch"], ctx.seed
    ref = drv.reference_run(cfg, seed, batch)
    if what == "control":
        other = drv.reference_run(cfg, seed, batch, precision=R.FP8)
    elif what == "control_bf16":
        other = drv.reference_run(cfg, seed, batch, precision=None, dtype=jnp.bfloat16)
    else:
        other = drv.reference_run(cfg, seed, batch, rows=batch // 2)
    prog = {"losses": other["losses"], "delta": other["delta"],
            "m1": {k: v * (1 - cfg["optimizer"]["b1"]) for k, v in other["grads"].items()}}
    nums, notes = drv.compare(prog, ref, cfg["optimizer"]["b1"])
    return {**nums, **notes}


def serve_reading(ctx, what: str) -> dict:
    import jax.numpy as jnp
    import numpy as np

    drv = RUN.load_module("drivers", "serve")
    p, cfg = ctx.params, ctx.cfg
    weights = drv._weights(cfg, ctx.seed)
    zs = np.random.default_rng([ctx.seed, 3]).standard_normal(
        (p["sample"] * p["size_max"], cfg["z_dim"]), np.float32)
    ref = drv.reference_images(cfg, weights, zs)
    low = (drv.reference_images(cfg, weights, zs, precision=R.FP8) if what == "control" else
           drv.reference_images(cfg, weights, zs, precision=None, dtype=jnp.bfloat16))
    out = {"img_rel_l2": 0.0, "img_rel_max": 0.0}
    for j in range(p["sample"]):
        rows = slice(j * p["size_max"], (j + 1) * p["size_max"])
        a, b = drv.image_gaps(low[rows], ref[rows])
        out = {"img_rel_l2": max(out["img_rel_l2"], a), "img_rel_max": max(out["img_rel_max"], b)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", choices=("program", "control", "control_bf16", "half_batch"), required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    _, entry, wl, cfg = RUN.load_cell(args.workload)
    from bench import system

    system.import_program()
    jax = RUN.configure_jax()
    RUN.device_info(jax, entry["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = RUN.Context(args.workload, entry, wl, cfg, seed, args.seconds, False)
        if args.what == "program":
            out = RUN.load_module("drivers", wl["driver"]).run(ctx)
            nums = {**{k: v for k, (v, _) in out["checks"].items()}, **out.get("notes", {})}
        elif wl["driver"] == "train":
            nums = train_reading(ctx, args.what)
        else:
            nums = serve_reading(ctx, args.what)
        print(json.dumps({"seed": seed, "what": args.what, **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
