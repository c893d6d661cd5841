"""Plain float32 reference of the benchmark's GANs, written from the papers.

Nothing here imports the system under test.  It computes, in straight
``jax.numpy``:

* the training data (smooth synthetic images and normal latents, a pure
  function of ``(seed, step)``) and the weight init of the configuration;
* the generator and discriminator as they are trained on the engines: every
  stride-2 deconv is split into its S^2 sub-filters (TDC, arXiv:1911.06918
  Fig. 2b) and every stride-2 conv into its S^2 input phases, each run as a
  Winograd F(2x2, 3x3) correlation (paper eq. 3-4) whose transformed
  weights ``U = G g G^T`` are the trained parameters.  Only the structurally
  nonzero positions of U are stored (49 of 64 for K5S2, 36 for K4S2, 16 for
  K3S1), row-major over (sub-filter, u, v): the packed layout;
* one adversarial AdamW step (the non-saturating losses, two gradient pulls
  from one forward, batch-stat BN whose running stats follow with momentum
  0.9);
* the eval-mode generator from raw deconv weights, as a transposed
  convolution (``lax.conv_general_dilated`` with lhs dilation), for serving.

``precision`` is the matmul precision: ``HIGHEST`` for the reference, or
``FP8``, which rounds every matmul operand of the forward pass (the channel
contractions, the linear layers) to float8_e4m3fn under a per-tensor scale,
for the lower-precision control; gradients flow through the rounding
unchanged, so the backward passes multiply the rounded operands by
unrounded cotangents.  ``dtype`` is the compute type.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = "fp8"
CONFIG_DIR = Path(__file__).resolve().parent / "configs"

# F(2x2, 3x3): arXiv:1911.06918 eq. (3)
BT = np.array([[1, 0, -1, 0], [0, 1, 1, 0], [0, -1, 1, 0], [0, 1, 0, -1]], np.float32)
GM = np.array([[1, 0, 0], [0.5, 0.5, 0.5], [0.5, -0.5, 0.5], [0, 0, 1]], np.float32)
AT = np.array([[1, 1, 1, 0], [0, 1, -1, -1]], np.float32)
TM, TR, TN = 2, 3, 4  # output tile m, filter taps r, input tile n = m + r - 1


def _fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8_e4m3fn under a per-tensor scale (largest
    magnitude to 448); the gradient passes the rounding unchanged."""
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / 448.0)
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _operands(precision, *xs):
    """(precision, operands) of one matmul: under ``FP8`` the operands are
    rounded to float8 and multiplied exactly."""
    if precision == FP8:
        return HIGHEST, tuple(_fp8(x) for x in xs)
    return precision, xs


def _exact(precision):
    """Precision of the transforms and other non-matmul contractions."""
    return HIGHEST if precision == FP8 else precision


def load_config(name: str) -> dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


# ------------------------------------------------------------- geometry
def deconv_out(h: int, d: dict) -> int:
    return d["stride"] * (h - 1) + d["kernel"] - 2 * d["padding"] + d["output_padding"]


def same_conv(k: int, s: int, h: int) -> dict:
    """SAME padding of a stride-s conv on an h-pixel input, low side first."""
    out = -(-h // s)
    total = max((out - 1) * s + k - h, 0)
    return {"kernel": k, "stride": s, "pad_lo": total // 2, "pad_hi": total - total // 2}


def conv_out(h: int, c: dict) -> int:
    return (h + c["pad_lo"] + c["pad_hi"] - c["kernel"]) // c["stride"] + 1


def disc_geometry(cfg: dict) -> list[dict]:
    h, out = cfg["img_hw"], []
    for _ in cfg["disc_channels"]:
        c = same_conv(cfg["disc_kernel"], cfg["disc_stride"], h)
        out.append(c)
        h = conv_out(h, c)
    return out


def _deconv_taps(d: dict) -> np.ndarray:
    """(S, S, r, r) -> flat index into the K*K raw taps, or -1 for none.

    Sub-filter rho = (ry, rx) holds taps k = rho + S*t, flipped inside the
    K_C = ceil(K/S) window and zero-padded to r at the high end, so that each
    sub-problem is a cross-correlation of the input padded by K_C - 1."""
    k, s = d["kernel"], d["stride"]
    kc = -(-k // s)
    idx = np.full((s, s, TR, TR), -1, np.int64)
    for ry in range(s):
        for rx in range(s):
            for ty in range(math.ceil((k - ry) / s)):
                for tx in range(math.ceil((k - rx) / s)):
                    idx[ry, rx, kc - 1 - ty, kc - 1 - tx] = (ry + s * ty) * k + rx + s * tx
    return idx


def _conv_taps(c: dict) -> np.ndarray:
    """(S, S, r, r) -> flat raw tap index (-1 for none) of the phase
    sub-filters: tap k = rho + S*t reads input phase (rho - P) mod S with
    shift floor((rho - P) / S), every phase padded left by ceil(P / S)."""
    k, s, p = c["kernel"], c["stride"], c["pad_lo"]
    lpad = -(-p // s)
    idx = np.full((s, s, TR, TR), -1, np.int64)
    for ry in range(s):
        uy0 = (ry - p - (ry - p) % s) // s + lpad
        for rx in range(s):
            ux0 = (rx - p - (rx - p) % s) // s + lpad
            for ty in range(math.ceil((k - ry) / s)):
                for tx in range(math.ceil((k - rx) / s)):
                    idx[ry, rx, uy0 + ty, ux0 + tx] = (ry + s * ty) * k + rx + s * tx
    return idx


def _packed_positions(taps: np.ndarray) -> np.ndarray:
    """Flat (S*S*n*n) positions of U that some raw tap reaches, in order."""
    present = (taps >= 0).astype(np.float64)  # (S, S, r, r)
    reach = np.einsum("ua,rqab,vb->rquv", np.abs(GM), present, np.abs(GM)) > 0
    return np.flatnonzero(reach.reshape(-1))


def _transform(w: jax.Array, taps: np.ndarray) -> jax.Array:
    """Raw (K, K, N, M) weights -> packed (C, N, M) Winograd-domain weights."""
    k = w.shape[0]
    flat = jnp.concatenate([w.reshape(k * k, *w.shape[2:]),
                            jnp.zeros((1, *w.shape[2:]), w.dtype)])
    g = jnp.take(flat, jnp.asarray(np.where(taps < 0, k * k, taps).reshape(-1)), axis=0)
    g = g.reshape(*taps.shape, *w.shape[2:])  # (S, S, r, r, N, M)
    u = jnp.einsum("ua,rqabnm,vb->rquvnm", GM, g, GM, precision=HIGHEST)
    u = u.reshape(-1, *w.shape[2:])
    return jnp.take(u, jnp.asarray(_packed_positions(taps)), axis=0)


def _unpack(ww: jax.Array, taps: np.ndarray) -> jax.Array:
    s = taps.shape[0]
    dense = jnp.zeros((s * s * TN * TN, *ww.shape[1:]), ww.dtype)
    dense = dense.at[jnp.asarray(_packed_positions(taps))].set(ww)
    return dense.reshape(s, s, TN, TN, *ww.shape[1:])


def _tiles(xp: jax.Array, t: int) -> jax.Array:
    """Padded (B, 2t+2, 2t+2, N) -> the overlapping n x n tiles at stride m,
    (B, t, t, n, n, N): tile i spans the m-blocks i and i + 1."""
    b, n = xp.shape[0], xp.shape[-1]
    blk = xp.reshape(b, t + 1, TM, t + 1, TM, n)
    rows = jnp.concatenate([blk[:, :t], blk[:, 1:]], axis=2)
    z = jnp.concatenate([rows[:, :, :, :t], rows[:, :, :, 1:]], axis=4)
    return z.transpose(0, 1, 3, 2, 4, 5)


def _winograd(z: jax.Array, u: jax.Array, precision) -> jax.Array:
    """Tiles (B, t, t, n, n, N) x filters (..., n, n, N, M) ->
    (..., B, 2t, 2t, M): Y = A^T [sum_N (U * B^T Z B)] A per tile."""
    bt, at = jnp.asarray(BT, z.dtype), jnp.asarray(AT, z.dtype)
    v = jnp.einsum("ui,btsijn,vj->btsuvn", bt, z, bt, precision=_exact(precision))
    lead = u.shape[:-4]
    prec, (v, uf) = _operands(precision, v, u.reshape(-1, *u.shape[-4:]))
    mm = jnp.einsum("btsuvn,kuvnm->kbtsuvm", v, uf, precision=prec)
    y = jnp.einsum("au,kbtsuvm,cv->kbtascm", at, mm, at, precision=_exact(precision))
    k, b, t = y.shape[0], y.shape[1], y.shape[2]
    return y.reshape(*lead, b, TM * t, TM * t, y.shape[-1])


def deconv_packed(x: jax.Array, ww: jax.Array, d: dict, precision=HIGHEST) -> jax.Array:
    """Transposed conv (out[S*i + k - P] += x[i] w[k]) with packed weights."""
    b, h, _, _ = x.shape
    s, p, kc = d["stride"], d["padding"], -(-d["kernel"] // d["stride"])
    ho = deconv_out(h, d)
    hj = (ho - 1 + p) // s + 1
    t = -(-hj // TM)
    need = TM * (t - 1) + TN
    hi = max(0, need - (h + kc - 1))
    xp = jnp.pad(x, ((0, 0), (kc - 1, hi), (kc - 1, hi), (0, 0)))[:, :need, :need]
    u = _unpack(ww, _deconv_taps(d)).astype(x.dtype)
    y = _winograd(_tiles(xp, t), u, precision)[:, :, :, :hj, :hj]  # (S,S,B,hj,hj,M)
    full = jnp.transpose(y, (2, 3, 0, 4, 1, 5)).reshape(b, hj * s, hj * s, -1)
    return full[:, p:p + ho, p:p + ho]


def conv_packed(x: jax.Array, ww: jax.Array, c: dict, precision=HIGHEST) -> jax.Array:
    """Stride-S cross-correlation (out[o] = sum_k w[k] x[S*o + k - P]) with
    packed weights: the S^2 phase sub-correlations are summed."""
    b, h, _, n = x.shape
    s, p = c["stride"], c["pad_lo"]
    lpad = -(-p // s)
    ho = conv_out(h, c)
    t = -(-ho // TM)
    need = TM * (t - 1) + TN
    hp = -(-h // s)
    xs = jnp.pad(x, ((0, 0), (0, s * hp - h), (0, s * hp - h), (0, 0)))
    phases = xs.reshape(b, hp, s, hp, s, n)
    u = _unpack(ww, _conv_taps(c)).astype(x.dtype)
    out = 0.0
    for ry in range(s):
        for rx in range(s):
            ph = phases[:, :, (ry - p) % s, :, (rx - p) % s]
            hi = max(0, need - lpad - hp)
            xp = jnp.pad(ph, ((0, 0), (lpad, hi), (lpad, hi), (0, 0)))[:, :need, :need]
            out = out + _winograd(_tiles(xp, t), u[ry, rx], precision)
    return out[:, :ho, :ho]


def deconv_raw(x: jax.Array, w: jax.Array, d: dict, precision=HIGHEST) -> jax.Array:
    """Transposed conv from raw (K, K, N, M) weights, as XLA's dilated conv."""
    k, s, p, op = d["kernel"], d["stride"], d["padding"], d["output_padding"]
    lo, hi = k - 1 - p, k - 1 - p + op
    prec, (x, w) = _operands(precision, x, w)
    return jax.lax.conv_general_dilated(
        x, jnp.flip(w, (0, 1)), (1, 1), [(lo, hi), (lo, hi)], lhs_dilation=(s, s),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)


# ------------------------------------------------------------- layers
def _act(name: str, x):
    if name == "relu":
        return jnp.maximum(x, 0)
    if name == "tanh":
        return jnp.tanh(x)
    if name == "leaky_relu":
        return jnp.where(x >= 0, x, 0.2 * x)
    if name == "none":
        return x
    raise ValueError(name)


def _bn_train(p: dict, x: jax.Array, eps: float = 1e-5):
    mu = x.mean((0, 1, 2))
    var = jnp.square(x - mu).mean((0, 1, 2))
    y = (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)
    return y, {"mean": mu.astype(jnp.float32), "var": var.astype(jnp.float32)}


def _bn_eval(p: dict, x: jax.Array, eps: float = 1e-5):
    a = p["scale"] * jax.lax.rsqrt(p["var"] + eps)
    return x * a.astype(x.dtype) + (p["bias"] - p["mean"] * a).astype(x.dtype)


def _linear(p: dict, x: jax.Array, precision) -> jax.Array:
    prec, (x2, w) = _operands(precision, x, p["w"].astype(x.dtype))
    return jnp.dot(x2, w, precision=prec) + p["b"].astype(x.dtype)


def _stem(gp: dict, cfg: dict, z: jax.Array, precision) -> jax.Array:
    h = _linear(gp["stem"], z, precision)
    return h.reshape(z.shape[0], cfg["seed_hw"], cfg["seed_hw"], cfg["stem_ch"])


def generator_train(gp: dict, cfg: dict, z: jax.Array, precision=HIGHEST):
    """Training-mode generator on packed weights; returns (image, bn stats)."""
    stats = {}
    h, stats["stem_bn"] = _bn_train(gp["stem_bn"], _stem(gp, cfg, z, precision))
    h = _act("relu", h)
    for i, d in enumerate(cfg["deconvs"]):
        h = deconv_packed(h, gp[f"deconv{i}"]["ww"].astype(h.dtype), d, precision)
        if d["norm"] == "batch":
            h, stats[f"deconv{i}_bn"] = _bn_train(gp[f"deconv{i}_bn"], h)
        h = _act(d["act"], h)
    return h, stats


def discriminator_train(dp: dict, cfg: dict, img: jax.Array, precision=HIGHEST):
    """Training-mode discriminator on packed weights; returns (logit, stats)."""
    stats, h = {}, img
    for i, c in enumerate(disc_geometry(cfg)):
        wd = dp[f"conv{i}"]
        h = conv_packed(h, wd["ww"].astype(h.dtype), c, precision) + wd["b"].astype(h.dtype)
        if f"conv{i}_bn" in dp:
            h, stats[f"conv{i}_bn"] = _bn_train(dp[f"conv{i}_bn"], h)
        h = _act("leaky_relu", h)
    return _linear(dp["head"], h.reshape(h.shape[0], -1), precision), stats


def generator_eval_raw(gp: dict, cfg: dict, z: jax.Array, precision=HIGHEST) -> jax.Array:
    """Eval-mode generator (running-stat BN) on raw deconv weights."""
    h = _act("relu", _bn_eval(gp["stem_bn"], _stem(gp, cfg, z, precision)))
    for i, d in enumerate(cfg["deconvs"]):
        h = deconv_raw(h, gp[f"deconv{i}"]["w"].astype(h.dtype), d, precision)
        if d["norm"] == "batch":
            h = _bn_eval(gp[f"deconv{i}_bn"], h)
        h = _act(d["act"], h)
    return h


# ------------------------------------------------------------- init
def _bn_init(c: int) -> dict:
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,)),
            "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}


def _lecun(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _linear_init(key, d_in: int, d_out: int) -> dict:
    kw, _ = jax.random.split(key)
    return {"w": _lecun(kw, (d_in, d_out), d_in), "b": jnp.zeros((d_out,))}


def generator_init(key, cfg: dict, packed: bool) -> dict:
    """DCGAN-style init: lecun-normal stem, N(0, 0.02) deconv taps, unit BN;
    ``packed`` stores the deconv taps in the Winograd domain."""
    keys = jax.random.split(key, 2 + len(cfg["deconvs"]))
    p = {"stem": _linear_init(keys[0], cfg["z_dim"], cfg["seed_hw"] ** 2 * cfg["stem_ch"]),
         "stem_bn": _bn_init(cfg["stem_ch"])}
    for i, d in enumerate(cfg["deconvs"]):
        k = d["kernel"]
        w = 0.02 * jax.random.normal(keys[1 + i], (k, k, d["c_in"], d["c_out"]), jnp.float32)
        p[f"deconv{i}"] = {"ww": _transform(w, _deconv_taps(d))} if packed else {"w": w}
        if d["norm"] == "batch":
            p[f"deconv{i}_bn"] = _bn_init(d["c_out"])
    return p


def discriminator_init(key, cfg: dict) -> dict:
    """Lecun-normal convs (packed), zero biases, unit BN from layer 1, and a
    lecun-normal linear head on the flattened last feature map."""
    chans = [cfg["img_ch"], *cfg["disc_channels"]]
    keys = jax.random.split(key, len(chans))
    k = cfg["disc_kernel"]
    p, h = {}, cfg["img_hw"]
    for i, c in enumerate(disc_geometry(cfg)):
        w = _lecun(keys[i], (k, k, chans[i], chans[i + 1]), k * k * chans[i])
        p[f"conv{i}"] = {"ww": _transform(w, _conv_taps(c)), "b": jnp.zeros((chans[i + 1],))}
        if i > 0:
            p[f"conv{i}_bn"] = _bn_init(chans[i + 1])
        h = conv_out(h, c)
    p["head"] = _linear_init(keys[-1], h * h * chans[-1], 1)
    return p


def train_init(key, cfg: dict) -> tuple[dict, dict]:
    """Generator and discriminator from the key ``PRNGKey(seed)``."""
    kg, kd = jax.random.split(key)
    return generator_init(kg, cfg, packed=True), discriminator_init(kd, cfg)


# ------------------------------------------------------------- data
def _data_key(seed: int, step: int, tag: int):
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), step), tag)


def latent_batch(seed: int, step: int, batch: int, z_dim: int) -> jax.Array:
    return jax.random.normal(_data_key(seed, step, 0), (batch, z_dim), jnp.float32)


def image_batch(seed: int, step: int, batch: int, hw: int, ch: int = 3) -> jax.Array:
    """Smooth 'real' images in [-1, 1]: tanh of six random low-frequency
    separable Fourier modes per image and channel."""
    k1, k2, k3 = jax.random.split(_data_key(seed, step, 1), 3)
    modes = 6
    freq = jax.random.uniform(k1, (batch, modes, 2, ch), minval=0.5, maxval=3.0)
    phase = jax.random.uniform(k2, (batch, modes, 2, ch), maxval=2 * jnp.pi)
    amp = jax.random.normal(k3, (batch, modes, ch)) / modes
    yy = jnp.linspace(0, 2 * jnp.pi, hw)
    img = jnp.zeros((batch, hw, hw, ch))
    for m in range(modes):
        wy = jnp.sin(freq[:, m, 0, None, :] * yy[None, :, None] + phase[:, m, 0, None, :])
        wx = jnp.sin(freq[:, m, 1, None, :] * yy[None, :, None] + phase[:, m, 1, None, :])
        img = img + amp[:, m, None, None, :] * wy[:, :, None, :] * wx[:, None, :, :]
    return jnp.tanh(img)


# ------------------------------------------------------------- training
def _bce(logit, target):
    return jnp.mean(jnp.maximum(logit, 0) - logit * target + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def train_step(state: dict, z, real, cfg: dict, *, precision=HIGHEST, dtype=jnp.float32,
               rows: int | None = None):
    """One simultaneous G/D AdamW step.  ``state`` holds gp, dp and the two
    AdamW states (m, v, t).  ``rows`` keeps only the first rows of the batch
    (a planted fault).  Returns (new state, g_loss, d_loss, g_grads, d_grads)."""
    opt = cfg["optimizer"]
    if rows is not None:
        z, real = z[:rows], real[:rows]
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)

    def losses(gp, dp):
        fake, gs = generator_train(cast(gp), cfg, z.astype(dtype), precision)
        d_fake, _ = discriminator_train(cast(dp), cfg, fake, precision)
        d_real, ds = discriminator_train(cast(dp), cfg, real.astype(dtype), precision)
        gl = _bce(d_fake, jnp.ones_like(d_fake))
        dl = 0.5 * (_bce(d_real, jnp.ones_like(d_real)) + _bce(d_fake, jnp.zeros_like(d_fake)))
        return (gl.astype(jnp.float32), dl.astype(jnp.float32)), (gs, ds)

    (gl, dl), vjp, (gs, ds) = jax.vjp(losses, state["gp"], state["dp"], has_aux=True)
    one, zero = jnp.ones_like(gl), jnp.zeros_like(dl)
    g_grads, _ = vjp((one, zero))
    _, d_grads = vjp((zero, one))
    new = dict(state)
    for net, grads, stats in (("g", g_grads, gs), ("d", d_grads, ds)):
        p, m, v, t = state[f"{net}p"], state[f"{net}m"], state[f"{net}v"], state[f"{net}t"] + 1
        b1, b2 = opt["b1"], opt["b2"]
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        m2 = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32), m, grads)
        v2 = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g.astype(jnp.float32)), v, grads)
        p2 = jax.tree.map(
            lambda p_, m_, v_: p_ - opt["lr"] * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + opt["eps"]), p, m2, v2)
        for k, s in stats.items():
            p2[k] = {**p2[k], "mean": 0.9 * p[k]["mean"] + 0.1 * s["mean"],
                     "var": 0.9 * p[k]["var"] + 0.1 * s["var"]}
        new.update({f"{net}p": p2, f"{net}m": m2, f"{net}v": v2, f"{net}t": t})
    return new, gl, dl, g_grads, d_grads


def init_state(key, cfg: dict) -> dict:
    gp, dp = train_init(key, cfg)
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    return {"gp": gp, "dp": dp, "gm": zeros(gp), "gv": zeros(gp), "gt": jnp.float32(0),
            "dm": zeros(dp), "dv": zeros(dp), "dt": jnp.float32(0)}
