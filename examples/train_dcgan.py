"""End-to-end driver: train DCGAN (the paper's flagship workload) with the
Winograd engine pipeline on synthetic data, with checkpointing.

Default impls are the current fastest path — chained engine generator AND
chained Winograd-Conv discriminator, so the quickstart's full adversarial
train step (both nets, both grads) runs in the engine domain.  Default
model is a width-reduced DCGAN; --full uses the exact 1024-512-256-128
generator (~12.7M params).  The default impls need a TPU; on a CPU host
name the interpret twins explicitly.

Run:  PYTHONPATH=src python examples/train_dcgan.py --steps 200
      PYTHONPATH=src JAX_PLATFORMS=cpu python examples/train_dcgan.py \
          --impl pallas_chained_interpret --disc-impl pallas_chained_interpret
"""
import argparse
import dataclasses

from repro.configs.gan_zoo import DCGAN
from repro.device import enable_compile_cache, require_tpu
from repro.train.trainer import train_gan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--full", action="store_true", help="full-width DCGAN")
    ap.add_argument("--width-div", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_dcgan_ckpt")
    ap.add_argument("--impl", default="auto",
                    choices=["auto",
                             "ref", "pallas_interpret", "tdc", "zero_padded", "lax",
                             # Winograd-domain training: params are the packed
                             # transformed weights, bwd = Pallas engines
                             "prepacked_ref", "pallas_prepacked_interpret",
                             "pallas_fused_pre_prepacked_interpret",
                             # the current fastest: whole trunk chained in
                             # the engine domain (two-pass BN in training)
                             "pallas_chained", "pallas_chained_interpret"])
    ap.add_argument("--disc-impl", default="auto",
                    choices=["auto", "lax", "ref", "pallas_interpret",
                             "prepacked_ref", "pallas_prepacked_interpret",
                             "chained_ref",
                             "pallas_chained", "pallas_chained_interpret"])
    args = ap.parse_args()

    # "auto" is the engine-chained pipeline (generator AND discriminator
    # fully in the engine domain) compiled for the TPU; off the TPU name an
    # *_interpret impl explicitly
    if "auto" in (args.impl, args.disc_impl):
        require_tpu("--impl/--disc-impl auto (pallas_chained)")
    enable_compile_cache()
    impl = "pallas_chained" if args.impl == "auto" else args.impl
    disc_impl = "pallas_chained" if args.disc_impl == "auto" else args.disc_impl

    cfg = DCGAN
    if not args.full:
        d = args.width_div
        cfg = dataclasses.replace(
            cfg,
            stem_ch=DCGAN.stem_ch // d,
            deconvs=tuple(
                dataclasses.replace(
                    s, c_in=max(3, s.c_in // d), c_out=(3 if s.c_out == 3 else s.c_out // d)
                )
                for s in DCGAN.deconvs
            ),
            disc_channels=tuple(max(8, c // d) for c in DCGAN.disc_channels),
        )
    cfg = dataclasses.replace(cfg, deconv_impl=impl, conv_impl=disc_impl)

    out = train_gan(
        cfg,
        steps=args.steps,
        batch=args.batch,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=50,
        log_every=10,
        hooks=__import__("repro.train.trainer", fromlist=["TrainHooks"]).TrainHooks(
            on_step=lambda s, m: print(
                f"step {s:5d}  g_loss {m['g_loss']:.4f}  d_loss {m['d_loss']:.4f}"
            )
        ),
    )
    print(f"finished at step {out['final_step']}")


if __name__ == "__main__":
    main()
