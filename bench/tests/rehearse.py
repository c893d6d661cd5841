"""Drive a benchmark cell on the CPU at a tiny size with interpret-mode
kernels: the harness's own path except the look for a chip.  Used by the
tests; ``python3 -m bench.tests.rehearse <workload>`` runs one by hand."""
from __future__ import annotations

import copy
import json
import sys
import time

from bench import run as RUN

TINY_CHANNELS = {
    "dcgan": ([(16, 8), (8, 8), (8, 8), (8, 3)], 16),
    "artgan": ([(16, 8), (8, 8), (8, 8), (8, 8), (8, 3)], 16),
}


def tiny_config(cfg: dict, impl: str = "pallas_chained_interpret") -> dict:
    """The configuration with 8- and 16-channel layers and interpret-mode
    engines: the same geometry, a size the CPU can run."""
    cfg = copy.deepcopy(cfg)
    chans, stem = TINY_CHANNELS[cfg["name"]]
    cfg["stem_ch"] = stem
    for d, (a, b) in zip(cfg["deconvs"], chans):
        d["c_in"], d["c_out"] = a, b
    cfg["disc_channels"] = [8, 8, 8, 8]
    cfg["deconv_impl"] = cfg["conv_impl"] = impl
    return cfg


class CpuContext(RUN.Context):
    def __init__(self, name: str, seed: int, seconds: float, *, trace: bool = False,
                 params: dict | None = None, limits: dict | None = None,
                 impl: str = "pallas_chained_interpret"):
        wl, cfg = RUN.load_workload(name)
        wl["params"].update(params or {})
        wl["limits"].update(limits or {})
        super().__init__(name, wl, wl, tiny_config(cfg, impl), seed, seconds, trace)
        self.t_process = time.perf_counter()


def rehearse(name: str, seed: int = 2**31 + 11, seconds: float = 2.0, **kw) -> dict:
    ctx = CpuContext(name, seed, seconds, **kw)
    return RUN.load_module("drivers", ctx.workload["driver"]).run(ctx)


if __name__ == "__main__":
    small = {"batch": 4} if "train" in sys.argv[1] else {}
    out = rehearse(sys.argv[1], seconds=float(sys.argv[2]) if len(sys.argv) > 2 else 2.0,
                   params=small)
    out.pop("work", None)
    print(json.dumps({k: v for k, v in out.items() if not isinstance(v, list)}, default=str, indent=1))
