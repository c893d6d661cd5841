"""End-to-end driver: train any assigned architecture (reduced config on
CPU; the full config is exercised by the multi-pod dry-run).

Run:  PYTHONPATH=src python examples/train_lm.py --arch llama3-8b --steps 50
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import data as D
from repro.device import enable_compile_cache
from repro.configs import LMS, smoke_config
from repro.models import lm
from repro.optim import adamw_init, adamw_update
from repro.train import checkpoint as C


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=sorted(LMS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    params = lm.lm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    opt = adamw_init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{args.arch}: reduced config, {n_params/1e6:.2f}M params")

    @jax.jit
    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: lm.train_loss(p, cfg, batch, q_chunk=32, loss_chunk=32)
        )(params)
        params, opt, m = adamw_update(params, grads, opt, lr=3e-4, max_grad_norm=1.0)
        return params, opt, loss

    start = 0
    if args.ckpt_dir and (last := C.latest_step(args.ckpt_dir)) is not None:
        tree = C.restore_checkpoint(args.ckpt_dir, last, {"params": params, "opt": opt})
        params, opt, start = tree["params"], tree["opt"], last
        print(f"resumed from step {start}")

    t0 = time.time()
    for s in range(start, args.steps):
        if cfg.frontend == "stub_embeds":
            batch = {
                "embeds": D.embed_batch(0, s, args.batch, args.seq, cfg.d_model),
                "labels": D.lm_batch(0, s, args.batch, args.seq, cfg.vocab)["labels"],
                "positions": jnp.broadcast_to(
                    jnp.arange(args.seq)[None, :, None], (args.batch, args.seq, 3)
                ),
            }
        else:
            batch = D.lm_batch(0, s, args.batch, args.seq, cfg.vocab)
        params, opt, loss = step(params, opt, batch)
        if (s + 1) % 10 == 0:
            print(f"step {s+1:4d}  loss {float(loss):.4f}  ({(time.time()-t0)/(s+1-start):.2f}s/step)")
        if args.ckpt_dir and (s + 1) % 25 == 0:
            C.save_checkpoint(args.ckpt_dir, s + 1, {"params": params, "opt": opt})
    print("done")


if __name__ == "__main__":
    main()
