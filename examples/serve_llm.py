"""End-to-end serving driver: batched prefill + decode on any --arch.

Serves the reduced variant of an assigned architecture with a batch of
synthetic requests — the same prefill/serve_step the multi-pod dry-run
lowers at production shape.

  PYTHONPATH=src python examples/serve_llm.py --arch rwkv6-1.6b --tokens 32
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, lm_arch_ids
from repro.models.lm import init_params
from repro.models.lm.transformer import prefill
from repro.train.step import make_serve_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=lm_arch_ids())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch).reduced()
    print(f"serving {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab_size}")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B = args.batch
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, args.prompt_len)), jnp.int32)
    enc = None
    if cfg.encoder is not None:
        enc = jnp.asarray(
            rng.normal(size=(B, cfg.encoder.n_frames, cfg.d_model)) * 0.02,
            jnp.float32)

    max_seq = args.prompt_len + args.tokens + 8
    t0 = time.time()
    logits, cache = jax.jit(
        lambda p, t: prefill(cfg, p, t, max_seq, enc_embeds=enc)
    )(params, prompt)
    print(f"prefill: {B} x {args.prompt_len} tokens in "
          f"{time.time()-t0:.2f}s")

    serve = jax.jit(make_serve_step(cfg))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    outs = [tok]
    t0 = time.time()
    for _ in range(args.tokens):
        tok, _, cache = serve(params, tok, cache)
        outs.append(tok)
    dt = time.time() - t0
    gen = jnp.concatenate(outs, axis=1)
    print(f"decode : {args.tokens} steps x batch {B} in {dt:.2f}s "
          f"({args.tokens * B / dt:.1f} tok/s)")
    for b in range(min(B, 2)):
        print(f"  request {b}: {np.asarray(gen[b])[:16]} ...")


if __name__ == "__main__":
    main()
