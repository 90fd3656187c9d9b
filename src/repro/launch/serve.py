"""Serving launcher: batched generation with the OGB page pool.

    python -m repro.launch.serve --arch <id> [--policy ogb|lru|lfu|ftpl]
           [--steps N] [--batch B] [--prompt-len L] [--pool-pages C]
"""

from __future__ import annotations

import argparse

import numpy as np

import jax

from repro import compile_cache
from repro.configs.base import get_smoke
from repro.core.policies import make_policy
from repro.models.model import init_params
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import PagedKVPool


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--policy", default="ogb")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--pool-pages", type=int, default=32)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--hot-prompts", type=int, default=6)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_smoke(args.arch)
    params = init_params(cfg, jax.random.key(0))
    touches = args.steps * args.batch * (args.prompt_len // args.page_size)
    kw = {}
    if args.policy == "ogb":
        kw = {"horizon": touches, "batch_size": args.batch * (args.prompt_len // args.page_size)}
    elif args.policy == "ftpl":
        kw = {"horizon": touches}
    policy = make_policy(args.policy, 1 << 18, args.pool_pages, **kw)
    pool = PagedKVPool(policy, page_size=args.page_size)
    engine = ServeEngine(cfg, params, pool=pool, max_len=args.prompt_len + args.new_tokens)

    rng = np.random.default_rng(0)
    hot = [rng.integers(1, cfg.vocab_size, args.prompt_len) for _ in range(args.hot_prompts)]
    for step in range(args.steps):
        prompts = []
        for b in range(args.batch):
            if b < args.batch // 2:
                prompts.append(hot[(step + b) % len(hot)])
            else:
                prompts.append(rng.integers(1, cfg.vocab_size, args.prompt_len))
        engine.generate(np.stack(prompts).astype(np.int32), args.new_tokens)
        if (step + 1) % 10 == 0:
            s, p = engine.stats, pool.stats
            print(
                f"[serve] step {step+1:>4} prefix-reuse {s.prefix_reuse:6.1%} "
                f"page-hits {p.page_hit_ratio:6.1%} occupancy {pool.occupancy():.0f}"
            )
    s = engine.stats
    print(
        f"[serve] done: {s.requests} requests, {s.decode_tokens} tokens decoded, "
        f"prefix reuse {s.prefix_reuse:.1%} with policy={args.policy}"
    )


if __name__ == "__main__":
    main()
