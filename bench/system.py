"""The one place the benchmark touches the system under test.

``src/`` of the checkout goes on the import path, and a configuration file
becomes the program's own ``GANConfig``.  Everything else the benchmark
takes from the program goes through the drivers: the entry points
(``train_gan``, ``GanServeEngine``, ``AsyncGanServer``), their request
stamps and counters, and the kernels in the trace.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def import_program():
    """Put the checkout's ``src`` on the path; raise if it is not there."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"no system under test at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def gan_config(cfg: dict, *, deconv_impl: str | None = None, conv_impl: str | None = None):
    """The program's ``GANConfig`` for a benchmark configuration file."""
    import_program()
    from repro.configs.base import DeconvSpec, GANConfig
    from repro.core.tdc import DeconvDims

    deconvs = tuple(
        DeconvSpec(d["c_in"], d["c_out"],
                   DeconvDims(d["kernel"], d["stride"], d["padding"], d["output_padding"]),
                   norm=d["norm"], act=d["act"])
        for d in cfg["deconvs"])
    return GANConfig(
        arch_id=cfg["name"], z_dim=cfg["z_dim"], seed_hw=cfg["seed_hw"],
        stem_ch=cfg["stem_ch"], deconvs=deconvs, img_ch=cfg["img_ch"],
        img_hw=cfg["img_hw"], deconv_impl=deconv_impl or cfg["deconv_impl"],
        conv_impl=conv_impl or cfg["conv_impl"],
        disc_channels=tuple(cfg["disc_channels"]))
