"""Serving driver: batched prefill + decode with KV caches.

Runs a real (smoke-scale on CPU) serving loop: a batch of requests is
prefilled, then decoded token-by-token with the per-arch cache structure
(ring-buffer local windows, MLA latent cache, RG-LRU/RWKV states).

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
        --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as tfm
from repro.train.steps import make_decode_step, make_prefill_step


@dataclasses.dataclass
class ServeResult:
    tokens: np.ndarray           # (B, gen)
    prefill_s: float
    decode_s: float
    tokens_per_sec: float


def serve_batch(arch: str, *, smoke: bool = True, batch: int = 4,
                prompt_len: int = 64, gen: int = 32, max_len: int = 0,
                seed: int = 0, params=None, verbose: bool = True
                ) -> ServeResult:
    cfg = smoke_config(arch) if smoke else get_config(arch)
    mesh = make_local_mesh()
    max_len = max_len or (prompt_len + gen)
    if params is None:
        params = tfm.init_model(cfg, jax.random.PRNGKey(seed))

    rng = np.random.default_rng(seed)
    if cfg.input_kind == "tokens":
        prompts = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)}
    else:
        prompts = {"embeds": jnp.asarray(
            rng.standard_normal((batch, prompt_len, cfg.d_model)),
            jnp.float32)}

    prefill = jax.jit(make_prefill_step(cfg, max_len, mesh=mesh))
    decode = jax.jit(make_decode_step(cfg, mesh=mesh))

    cache = tfm.init_cache(cfg, batch, max_len)
    t0 = time.time()
    logits, cache = prefill(params, prompts, cache)
    next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t1 = time.time()

    out: List[np.ndarray] = []
    for _ in range(gen):
        out.append(np.asarray(next_tok))
        if cfg.input_kind == "tokens":
            step_in = {"tokens": next_tok[:, None]}
        else:
            # embeddings-stub archs feed the frontend embedding of the token
            emb = jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(seed), int(out[-1][0])),
                (batch, 1, cfg.d_model))
            step_in = {"embeds": emb}
        next_tok, cache = decode(params, step_in, cache)
    t2 = time.time()
    toks = np.stack(out, axis=1)
    dec_s = max(t2 - t1, 1e-9)
    r = ServeResult(tokens=toks, prefill_s=t1 - t0, decode_s=dec_s,
                    tokens_per_sec=batch * gen / dec_s)
    if verbose:
        print(f"{arch}: prefill({batch}x{prompt_len})={r.prefill_s:.2f}s "
              f"decode {gen} steps={r.decode_s:.2f}s "
              f"({r.tokens_per_sec:.1f} tok/s)")
    return r


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()
    serve_batch(args.arch, smoke=not args.full, batch=args.batch,
                prompt_len=args.prompt_len, gen=args.gen)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
