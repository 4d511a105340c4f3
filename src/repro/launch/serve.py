"""Batched serving driver: prefill a batch of prompts, decode greedily.

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b --smoke \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs import ARCHS, get_config, get_smoke
from ..models import Model
from .compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    b, s = args.batch, args.prompt_len
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab)}
    if cfg.family == "encdec":
        batch["frames"] = 0.1 * jax.random.normal(
            key, (b, cfg.enc_len, cfg.d_model))
    if cfg.family == "vlm":
        batch["patches"] = 0.1 * jax.random.normal(
            key, (b, cfg.n_patches, 1024))

    pad_to = s + args.gen + (cfg.n_patches if cfg.family == "vlm" else 0)
    t0 = time.time()
    logits, cache = jax.jit(
        lambda p, bt: model.prefill(p, bt, pad_to=pad_to))(params, batch)
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    decode = jax.jit(model.decode_step)
    out = [tok]
    for _ in range(args.gen - 1):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    toks = jnp.concatenate(out, axis=1)
    dt = time.time() - t0
    print(f"generated {toks.shape} in {dt:.2f}s "
          f"({b * args.gen / dt:.1f} tok/s incl. compile)")
    print("sample:", toks[0, :16].tolist())


if __name__ == "__main__":
    main()
