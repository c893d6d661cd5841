"""Serving example: batched prefill + decode loop (greedy) for any arch.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch gemma3-12b --tokens 16
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import data as D
from repro.device import enable_compile_cache
from repro.configs import LMS, smoke_config
from repro.models import lm


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-12b", choices=sorted(LMS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    params = lm.lm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    max_len = args.prompt_len + args.tokens + 1

    if cfg.frontend == "stub_embeds":
        prompt = {"embeds": D.embed_batch(0, 0, args.batch, args.prompt_len, cfg.d_model)}
    else:
        prompt = {"tokens": D.lm_batch(0, 0, args.batch, args.prompt_len, cfg.vocab)["tokens"]}

    prefill = jax.jit(lambda p, b: lm.prefill(p, cfg, b, q_chunk=32, max_len=max_len))
    decode = jax.jit(
        lambda p, c, t, n: lm.decode_step(p, cfg, c, t, n)
    )

    t0 = time.time()
    logits, cache = prefill(params, prompt)
    print(f"prefill {args.batch}x{args.prompt_len}: {time.time()-t0:.2f}s")

    out_tokens = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.time()
    for i in range(args.tokens):
        if cfg.frontend == "stub_embeds":
            # feed the embedding of the sampled token via the stub table
            step_in = D.embed_batch(1, i, args.batch, 1, cfg.d_model)
        else:
            step_in = tok
        logits, cache = decode(params, cache, step_in, jnp.int32(args.prompt_len + i))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    dt = (time.time() - t0) / args.tokens
    print(f"decode: {dt*1e3:.1f} ms/token/batch  ({args.batch/dt:.1f} tok/s aggregate)")
    seqs = jnp.concatenate(out_tokens, axis=1)
    print("sampled token ids (greedy):")
    for b in range(args.batch):
        print(" ", seqs[b].tolist())


if __name__ == "__main__":
    main()
