"""Serving launcher: batched prefill + greedy decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.configs import get_config
from repro.launch import sharding as shg
from repro.launch.mesh import make_local_mesh
from repro.models import lm


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compat.enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_local_mesh(args.tp)
    tp = args.tp
    rng = np.random.default_rng(args.seed)

    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed), tp=tp)
    max_seq = args.prompt_len + args.gen
    cache = lm.init_cache(cfg, args.batch, max_seq, tp=tp)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32
    )
    kw = {}
    if cfg.family == "encdec":
        kw["frames"] = jnp.asarray(
            rng.normal(size=(args.batch, max(args.prompt_len // 2, 1), cfg.d_model)),
            jnp.dtype(cfg.dtype),
        )
    if cfg.family == "vlm":
        kw["patches"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.vlm_patches, cfg.d_model)),
            jnp.dtype(cfg.dtype),
        )

    @jax.jit
    def decode_step(params, cache, tok, pos):
        logits, cache = lm.forward_cached(params, cfg, cache, tok, pos, tp=tp)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt[:, None], cache

    t0 = time.perf_counter()
    logits, cache = lm.forward_cached(
        params, cfg, cache, prompts, jnp.int32(0), tp=tp, **kw
    )
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    jax.block_until_ready(tok)
    t_prefill = time.perf_counter() - t0

    offset = cfg.vlm_patches if cfg.family == "vlm" else 0
    outs = [tok]
    t0 = time.perf_counter()
    for i in range(args.gen - 1):
        pos = jnp.int32(offset + args.prompt_len + i)
        tok, cache = decode_step(params, cache, tok, pos)
        outs.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen = np.concatenate([np.asarray(t) for t in outs], axis=1)
    print("generated:", gen[:, :12].tolist())
    tokens = args.batch * (args.gen - 1)
    print(
        f"prefill {args.batch}x{args.prompt_len} in {t_prefill*1e3:.1f} ms; "
        f"decode {tokens} tok in {t_decode*1e3:.1f} ms "
        f"({tokens/max(t_decode,1e-9):.1f} tok/s)"
    )
    return gen


if __name__ == "__main__":
    main()
