"""Serving launcher: ``python -m repro.launch.serve --arch <id> [options]``.

Runs the continuous-batching engine over the two-tier paged KV cache with the
selected promotion policy (paper Policy1/Policy2) and prints per-request outputs +
tier statistics.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core import emucxl as ecxl
from repro.core.policy import make_policy
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as tf
from repro.serving.engine import ServingEngine


def build_engine(cfg: ArchConfig, *, num_slots: int, page_size: int,
                 max_batch: int, max_pages_per_seq: int, policy: str = "policy1",
                 seed: int = 0) -> ServingEngine:
    """The continuous-batching engine for `cfg` with random weights from `seed`.

    Its cold tier is the process-default emucxl instance. The weights are made
    inside jit, so only their final dtype is ever materialized."""
    if cfg.family in ("ssm", "hybrid") or not cfg.causal:
        raise ValueError(f"{cfg.name}: paged serving targets attention archs")
    lib = ecxl.default_instance()
    if not lib._initialized:
        lib.init()
    params = jax.jit(tf.init_params, static_argnums=1)(jax.random.PRNGKey(seed), cfg)
    return ServingEngine(
        params, cfg, num_slots=num_slots, page_size=page_size, max_batch=max_batch,
        max_pages_per_seq=max_pages_per_seq, policy=make_policy(policy),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--policy", default="policy1")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    eng = build_engine(
        cfg, num_slots=args.slots, page_size=args.page_size,
        max_batch=args.max_batch,
        max_pages_per_seq=-(-(args.prompt_len + args.max_new) // args.page_size),
        policy=args.policy,
    )
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        rid = eng.submit(list(rng.integers(0, cfg.vocab_size, args.prompt_len)),
                         max_new_tokens=args.max_new)
        print(f"submitted request {rid}")
    results = eng.run(max_steps=2000)
    for rid, toks in sorted(results.items()):
        print(f"request {rid}: generated {toks}")
    print("tier stats:", eng.tier_stats())


if __name__ == "__main__":
    main()
