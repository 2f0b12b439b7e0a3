"""Serving launcher: batched prefill + decode for any decode-capable arch.

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b --new-tokens 16

``--list-archs`` prints every registered arch with its serving capability
and exits 0 (the sanctioned way to probe for encoder-only archs from
scripts); asking to *serve* an encoder-only arch remains exit code 1.
``--seed`` makes the random prompts and parameter init reproducible.
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..configs import get_config, list_archs, reduced
from ..models import build_model, split_params
from ..train.train_step import build_decode_step, build_prefill_step
from .compile_cache import enable_compile_cache


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(),
                    help="arch to serve (required unless --list-archs)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0,
                    help="RNG seed for prompts and parameter init")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--list-archs", action="store_true",
                    help="list archs and their serving capability, exit 0")
    args = ap.parse_args()

    if args.list_archs:
        # Explicit listing: encoder-only archs are information, not misuse.
        for arch in list_archs():
            kind = "decode" if get_config(arch).supports_decode() else "encoder-only"
            print(f"{arch}: {kind}")
        return 0
    if args.arch is None:
        ap.error("--arch is required unless --list-archs is given")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not cfg.supports_decode():
        print(f"{args.arch} is encoder-only: no autoregressive serving path")
        return 1
    if not args.full:
        cfg = reduced(cfg)
    model = build_model(cfg)
    values, _ = split_params(model.init(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
    )
    max_len = args.prompt_len + args.new_tokens
    prefill = jax.jit(build_prefill_step(model, max_len=max_len))
    decode = jax.jit(build_decode_step(model), donate_argnums=1)

    inputs = {"tokens": prompts}
    if cfg.frontend == "patch":
        inputs["patch_embeds"] = jnp.zeros(
            (args.batch, cfg.frontend_len, cfg.frontend_dim),
            jnp.dtype(cfg.compute_dtype),
        )
    t0 = time.time()
    logits, cache = prefill(values, inputs)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    print(f"prefill {args.batch}x{args.prompt_len} in {time.time()-t0:.2f}s")
    pos0 = args.prompt_len + (cfg.frontend_len if cfg.frontend == "patch" else 0)
    out = [tok]
    t0 = time.time()
    for t in range(args.new_tokens - 1):
        logits, cache = decode(values, cache, tok, jnp.int32(pos0 + t))
        tok = jnp.argmax(logits[:, 0], -1)[:, None].astype(jnp.int32)
        out.append(tok)
    dt = time.time() - t0
    print(f"decoded {args.new_tokens} tok/seq in {dt:.2f}s "
          f"({args.new_tokens*args.batch/max(dt,1e-9):.1f} tok/s)")
    print("first sequence:", jnp.concatenate(out, 1)[0].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
