"""Batched serving driver: prefill then token-by-token decode.

CPU runs reduced configs end-to-end (real numerics); the full configs are
exercised through the dry-run (serve_step lowering). Demonstrates the
anycost serving story of Fig. 5d as well: ``--alpha`` serves a width-shrunk
sub-model extracted from the same checkpoint without retraining.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --batch 2 --prompt-len 32 --decode-tokens 16 --alpha 0.5
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.configs import get_config
from repro.core import shrinking
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model


def prefill_into_cache(model, params, tokens, cache_len):
    """Fill the decode cache from the prompt.

    Attention families use the batched one-pass prefill (models.transformer
    .prefill_lm — validated against the decode loop in tests/test_prefill);
    recurrent families (SSM/hybrid, O(1) state) step the decode path.
    """
    from repro.models import transformer as T
    cfg = model.cfg
    B, S = tokens.shape
    if cfg.family in ("dense", "vlm", "moe"):
        jpre = jax.jit(functools.partial(T.prefill_lm, cfg=cfg,
                                         cache_len=cache_len))
        return jpre(params, tokens)
    cache = model.init_cache(B, cache_len)
    jstep = jax.jit(model.decode)
    logits = None
    for t in range(S):
        logits, cache = jstep(params, cache, {"tokens": tokens[:, t:t + 1]})
    return logits, cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-tokens", type=int, default=16)
    ap.add_argument("--alpha", type=float, default=1.0,
                    help="anycost sub-model width for serving (Fig. 5d)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    if args.alpha < 1.0:
        spec = shrinking.transformer_shrink_spec(cfg, params)
        if spec.groups:
            sorted_p = shrinking.sort_channels(params, spec)
            params = shrinking.shrink(sorted_p, args.alpha, spec)
            cfg = shrinking.shrunk_config(cfg, args.alpha, spec)
            model = build_model(cfg)
            print(f"serving alpha={args.alpha} sub-model "
                  f"(widths: {spec.widths(args.alpha)})")
        else:
            print("arch has no shrinkable groups; serving full model")

    rng = np.random.default_rng(args.seed)
    cache_len = args.prompt_len + args.decode_tokens
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (args.batch, args.prompt_len)),
                         jnp.int32)
    # repro: ignore[unseeded-randomness] — wall-clock below *measures*
    # prefill/decode latency for the smoke-test report; it never feeds
    # model or simulation state.
    t0 = time.time()
    logits, cache = prefill_into_cache(model, params, prompt, cache_len)
    # repro: ignore[unseeded-randomness] — latency probe
    t_prefill = time.time() - t0

    jstep = jax.jit(model.decode)
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    out_tokens = [tok]
    # repro: ignore[unseeded-randomness] — latency probe
    t0 = time.time()
    for _ in range(args.decode_tokens - 1):
        logits, cache = jstep(params, cache, {"tokens": tok})
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        out_tokens.append(tok)
    # repro: ignore[unseeded-randomness] — latency probe
    t_decode = time.time() - t0
    gen = jnp.concatenate(out_tokens, axis=1)
    assert bool(jnp.all(jnp.isfinite(logits))), "non-finite logits"
    print(f"prefill {args.prompt_len} toks x{args.batch}: {t_prefill:.2f}s; "
          f"decode {args.decode_tokens} toks: {t_decode:.2f}s "
          f"({args.batch * (args.decode_tokens - 1) / max(t_decode, 1e-9):.1f} tok/s)")
    print("sample:", np.asarray(gen[0])[:16])


if __name__ == "__main__":
    main()
