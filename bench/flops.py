"""Operations and bytes the benchmark's GANs need, from their shapes alone.

Counts follow the direct definitions (after ``core/complexity.py``):

* a transposed conv is the scatter-sum: every input pixel multiplies every
  one of the K x K taps, ``H_in * W_in * K^2 * N * M`` MACs per image;
* a strided conv is ``H_out * W_out * K^2 * N * M`` MACs per image;
* a linear layer is ``N * M`` MACs per row.

A FLOP is 2 MACs, and each pass of a layer (forward, data gradient, weight
gradient) does the same MACs.  Bytes are the f32 operands and result of a
pass: activations in and out and the weights.  The Winograd engines do
fewer multiplies than this (527M of DCGAN's 639M generator MACs), so a
share against it counts the work as the layer defines it, whatever runs it.
"""
from __future__ import annotations

from . import reference as R

F32 = 4


def _layers(cfg: dict) -> tuple[list[dict], list[dict]]:
    """Per-image MACs and per-image activation / total weight elements of
    the generator and the discriminator layers."""
    gen = [{"name": "stem", "engine": False,
            "macs": cfg["z_dim"] * cfg["seed_hw"] ** 2 * cfg["stem_ch"],
            "act_in": cfg["z_dim"], "act_out": cfg["seed_hw"] ** 2 * cfg["stem_ch"],
            "weights": cfg["z_dim"] * cfg["seed_hw"] ** 2 * cfg["stem_ch"]}]
    h = cfg["seed_hw"]
    for i, d in enumerate(cfg["deconvs"]):
        ho = R.deconv_out(h, d)
        w = d["kernel"] ** 2 * d["c_in"] * d["c_out"]
        gen.append({"name": f"deconv{i}", "engine": True, "macs": h * h * w,
                    "act_in": h * h * d["c_in"], "act_out": ho * ho * d["c_out"], "weights": w})
        h = ho
    disc, h = [], cfg["img_hw"]
    chans = [cfg["img_ch"], *cfg["disc_channels"]]
    for i, c in enumerate(R.disc_geometry(cfg)):
        ho = R.conv_out(h, c)
        w = c["kernel"] ** 2 * chans[i] * chans[i + 1]
        disc.append({"name": f"conv{i}", "engine": True, "macs": ho * ho * w,
                     "act_in": h * h * chans[i], "act_out": ho * ho * chans[i + 1], "weights": w})
        h = ho
    feat = h * h * chans[-1]
    disc.append({"name": "head", "engine": False, "macs": feat, "act_in": feat,
                 "act_out": 1, "weights": feat})
    return gen, disc


def _pass(layer: dict, kind: str, rows: int, times: int = 1) -> dict:
    elems = rows * (layer["act_in"] + layer["act_out"]) + layer["weights"]
    return {"layer": layer["name"], "pass": kind, "engine": layer["engine"],
            "flops": 2 * layer["macs"] * rows * times, "bytes": F32 * elems * times}


def train_step_passes(cfg: dict, batch: int) -> list[dict]:
    """The passes one simultaneous G/D step must compute: the generator
    forward and both its gradients (none into the latent); the discriminator
    forward on the fake and the real batch, both weight gradients, and the
    data gradients that reach a weight or the generator: on the fake batch
    one per loss (two) and on the real batch one, none into the real images."""
    gen, disc = _layers(cfg)
    out = []
    for i, layer in enumerate(gen):
        out += [_pass(layer, "fwd", batch), _pass(layer, "wgrad", batch)]
        if i > 0:
            out.append(_pass(layer, "dgrad", batch))
    for i, layer in enumerate(disc):
        out += [_pass(layer, "fwd", batch, 2), _pass(layer, "wgrad", batch, 2)]
        out.append(_pass(layer, "dgrad", batch, 1 if i == 0 else 3))
    return out


def generate_passes(cfg: dict, images: int) -> list[dict]:
    """The generator forward over ``images`` images."""
    gen, _ = _layers(cfg)
    return [_pass(layer, "fwd", images) for layer in gen]


def total_flops(passes: list[dict]) -> float:
    return float(sum(p["flops"] for p in passes))


def engine_bound(passes: list[dict], peak: dict) -> tuple[float, str]:
    """Least time the engine passes (conv and deconv layers) could take on a
    chip with these peaks: per pass the larger of its FLOP and byte bounds,
    summed; and which of the two bounds the larger share of that time."""
    t_flop = t_byte = total = 0.0
    for p in passes:
        if not p["engine"]:
            continue
        f = p["flops"] / peak["bf16_flops_per_s"]
        b = p["bytes"] / peak["hbm_bytes_per_s"]
        total += max(f, b)
        if f >= b:
            t_flop += f
        else:
            t_byte += b
    return total, "flops" if t_flop >= t_byte else "bytes"
