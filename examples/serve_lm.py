"""Serve a small LM with batched requests: prefill a batch of prompts, then
greedy-decode continuations token-by-token through the KV cache engine.

Run:  PYTHONPATH=src python examples/serve_lm.py [--arch stablelm-3b]
      [--batch 4] [--prompt-len 32] [--gen 16]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data.tokens import batch_at
from repro.models import lm
from repro.serve import engine
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.smoke_config(args.arch)
    params, _ = lm.init(jax.random.key(0), cfg, {})
    print(f"serving {cfg.name}: {lm.count_params(cfg)/1e6:.1f}M params, "
          f"batch={args.batch}")

    prompts = batch_at(0, 0, args.batch, args.prompt_len, cfg.vocab)["tokens"]
    batch = {"tokens": jnp.asarray(prompts)}
    if cfg.family == "encdec":
        batch["enc_embed"] = jnp.zeros((args.batch, cfg.enc_len, cfg.d_model),
                                       jnp.bfloat16)
    if cfg.family == "vlm":
        batch["vision_embed"] = jnp.zeros(
            (args.batch, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16)

    prefill = jax.jit(lambda p, b: engine.prefill(cfg, p, b))
    decode = jax.jit(lambda p, c, t: engine.decode_step(cfg, p, c, t))

    t0 = time.time()
    cache, logits = prefill(params, batch)
    # grow attention caches to prompt+gen
    for k in ("k", "v", "kx_self", "vx_self"):
        if k in cache:
            pad = [(0, 0)] * cache[k].ndim
            pad[-3] = (0, args.gen)
            cache[k] = jnp.pad(cache[k], pad)
    print(f"prefill {args.prompt_len} tokens x {args.batch}: "
          f"{time.time()-t0:.2f}s")

    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    tok = jnp.minimum(tok, cfg.vocab - 1)
    generated = [np.asarray(tok)]
    t0 = time.time()
    for _ in range(args.gen - 1):
        cache, logits = decode(params, cache, tok)
        tok = jnp.minimum(jnp.argmax(logits, -1)[:, None].astype(jnp.int32),
                          cfg.vocab - 1)
        generated.append(np.asarray(tok))
    dt = time.time() - t0
    gen = np.concatenate(generated, axis=1)
    print(f"decoded {args.gen} tokens x {args.batch} in {dt:.2f}s "
          f"({args.batch*(args.gen-1)/max(dt,1e-9):.1f} tok/s, "
          f"{1000*dt/(args.gen-1):.0f} ms/step)")
    for i in range(min(2, args.batch)):
        print(f"  request {i}: prompt tail {prompts[i,-5:].tolist()} -> "
              f"generated {gen[i,:10].tolist()}")


if __name__ == "__main__":
    main()
