"""Paper Sec. IV-C: design-space exploration over tiling factors (T_m, T_n).

Enumerates (T_m, T_n) pairs, evaluates the paper's computational-roof /
bandwidth model (eqs. 5-9, core/complexity.dse_model) for DCGAN under the
paper's FPGA constants, and reports the Pareto choice — reproducing the
paper's selection of T_m=4, T_n=128.  A second sweep re-prices the model
with TPU v5e constants to show how the optimum moves when bandwidth is
200x higher (the DESIGN.md §2 hardware-adaptation note).

A third, *measured* sweep (kernels/autotune.py) times the Pallas engine's
real block-size design space — fused pre-PE vs unfused — because on TPU the
analytic model can't see Mosaic's scheduling.  On CPU it runs the kernels in
interpret mode on a small DCGAN-shaped layer (machinery check, not a perf
number); on a TPU backend the same sweep is the real DSE.
"""
from __future__ import annotations

from repro.core.complexity import dse_model
from repro.device import enable_compile_cache
from repro.core.tdc import DeconvDims
from repro.kernels.autotune import (
    EngineConfig, autotune_deconv, epilogue_candidates, small_candidates,
)

from .workloads import GAN_LAYERS

FPGA = dict(freq_hz=100e6, bandwidth=4e9)  # paper Sec. V-A
TPU = dict(freq_hz=940e6, bandwidth=819e9)  # v5e core clock / HBM


def sweep(constants: dict, dsp_budget: int = 2560) -> list[dict]:
    """DSP usage model: one multiplier per (T_m x T_n) lane set per position;
    the paper keeps T_m*T_n*... within the 2560 DSPs of [14]."""
    rows = []
    layers = GAN_LAYERS["dcgan"]
    for t_m in (1, 2, 4, 8, 16):
        for t_n in (16, 32, 64, 128, 256):
            if t_m * t_n > dsp_budget:
                continue
            roof = 0.0
            bw_req = 0.0
            for l in layers:
                m = dse_model(l, t_m=t_m, t_n=t_n, **constants)
                roof += m["computational_roof_ops"]
                bw_req = max(bw_req, m["bandwidth_req_Bps"])
            feasible = bw_req <= constants["bandwidth"]
            rows.append(
                {
                    "t_m": t_m,
                    "t_n": t_n,
                    "roof_gops": roof / 1e9,
                    "bw_req_GBps": bw_req / 1e9,
                    "feasible": feasible,
                }
            )
    return rows


def best(rows):
    feas = [r for r in rows if r["feasible"]]
    return max(feas or rows, key=lambda r: r["roof_gops"])


def engine_block_sweep(
    dims: DeconvDims | None = None,
    input_shape: tuple[int, int, int, int] = (1, 8, 8, 32),
    c_out: int = 32,
    candidates: list[EngineConfig] | None = None,
    mode: str = "fwd",
) -> list[dict]:
    """Measured engine DSE: fused pre-PE block sweep next to the unfused
    baseline.  ``mode='grad'`` times value_and_grad instead, sweeping the
    Pallas *backward* engines' design space.  Shapes default small so the
    CPU interpret-mode run stays in seconds; on TPU pass a real layer
    shape."""
    if dims is None:
        dims = DeconvDims(5, 2, 2, 1)  # DCGAN's K5S2 geometry
    if candidates is None:
        candidates = small_candidates()
    rows = autotune_deconv(dims, input_shape, c_out, candidates=candidates, mode=mode)
    for r in rows:
        c = r["config"]
        blk = f"block_ty={c.block_ty}" if c.fuse_pre else f"block_t={c.block_t}"
        status = f"ms={r['ms']:.2f}" if r["ok"] else f"error={r['error']}"
        print(
            f"dse,engine,mode={mode},pre_pe={'fused' if c.fuse_pre else 'unfused'},"
            f"{blk},block_n={c.block_n},block_m={c.block_m},"
            f"epilogue={c.epilogue or '-'},emit_cells={int(c.emit_cells)},{status}"
        )
    return rows


def main():
    enable_compile_cache()
    f = sweep(FPGA)
    b = best(f)
    print(f"dse,fpga,best_t_m={b['t_m']},best_t_n={b['t_n']},roof_gops={b['roof_gops']:.1f}"
          f",paper_choice=t_m=4/t_n=128")
    t = sweep(TPU, dsp_budget=1 << 30)
    bt = best(t)
    print(f"dse,tpu_v5e,best_t_m={bt['t_m']},best_t_n={bt['t_n']},roof_gops={bt['roof_gops']:.1f}")
    rows = engine_block_sweep()
    won = next((r for r in rows if r["ok"]), None)
    if won is not None:
        c = won["config"]
        print(
            f"dse,engine_best,pre_pe={'fused' if c.fuse_pre else 'unfused'},"
            f"block_n={c.block_n},block_m={c.block_m},ms={won['ms']:.2f}"
        )
    # Backward-engine DSE: same candidates timed through value_and_grad
    # (smaller shape — the grad graph runs three kernels per candidate).
    rows_g = engine_block_sweep(input_shape=(1, 6, 6, 16), c_out=16, mode="grad")
    won_g = next((r for r in rows_g if r["ok"]), None)
    if won_g is not None:
        c = won_g["config"]
        print(
            f"dse,engine_best_grad,pre_pe={'fused' if c.fuse_pre else 'unfused'},"
            f"block_n={c.block_n},block_m={c.block_m},ms={won_g['ms']:.2f}"
        )
    # Epilogue/chain DSE: scratch-out vs epilogue-fused NHWC vs cells-out,
    # so the chained-pipeline configs stay comparable with the classic ones.
    rows_e = engine_block_sweep(
        candidates=epilogue_candidates(block_ty=(2, 4))
    )
    won_e = next((r for r in rows_e if r["ok"]), None)
    if won_e is not None:
        c = won_e["config"]
        print(
            f"dse,engine_best_epilogue,epilogue={c.epilogue or '-'},"
            f"emit_cells={int(c.emit_cells)},block_ty={c.block_ty},"
            f"ms={won_e['ms']:.2f}"
        )


if __name__ == "__main__":
    main()
