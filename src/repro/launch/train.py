"""Training launcher: ``python -m repro.launch.train --arch <id> [options]``.

Wires together configs, mesh+rules, synthetic data, the AdamW step (offloaded state
per the paper's technique where configured), the fault-tolerant loop, and
checkpointing. On this CPU container use ``--reduced`` (full configs are for the
dry-run); on a real pod drop the flag and point --mesh at the slice.
"""

from __future__ import annotations

import argparse
import functools

import jax

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.data.loader import PrefetchLoader
from repro.data.synthetic import SyntheticTokens
from repro.distributed import axis_rules
from repro.launch import specs as sp
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import transformer as tf
from repro.optim import adamw
from repro.runtime.train_loop import TrainLoopConfig, run


def build_trainer(cfg: ArchConfig, mesh, rules: str, hp: adamw.OptimizerConfig,
                  opts: tf.ModelOptions, grad_accum: int = 1, seed: int = 0):
    """(step, params, opt_state) for `cfg` on `mesh`; call under ``axis_rules``.

    Parameters and optimizer state are made inside jit with their shardings as
    outputs, so each device only ever holds its own shards. The step donates both
    (a step's inputs and outputs never need to coexist)."""
    p_sh = sp.param_shardings(cfg, mesh, rules)
    o_sh = sp.opt_state_shardings(cfg, hp, mesh, rules)
    params = jax.jit(tf.init_params, static_argnums=1, out_shardings=p_sh)(
        jax.random.PRNGKey(seed), cfg)
    opt = jax.jit(functools.partial(adamw.init_state, hp=hp),
                  out_shardings=o_sh)(params)
    step = jax.jit(
        make_train_step(cfg, opts, hp, grad_accum=grad_accum),
        in_shardings=(p_sh, o_sh, None),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
    )
    return step, params, opt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="1x1", help="e.g. 2x4 => (data=2, model=4)")
    ap.add_argument("--rules", default="train_fsdp")
    ap.add_argument("--moe-impl", default="ep")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    hp = adamw.OptimizerConfig(learning_rate=args.lr, warmup_steps=10,
                               decay_steps=args.steps)

    with mesh, axis_rules(mesh, args.rules):
        step, params, opt = build_trainer(
            cfg, mesh, args.rules, hp, tf.ModelOptions(moe_impl=args.moe_impl),
            grad_accum=args.grad_accum,
        )
        src = SyntheticTokens(cfg, args.batch, args.seq, seed=0)
        loader = PrefetchLoader(src)

        def log(step_idx, metrics):
            print(f"step {step_idx:5d} loss={metrics['loss']:.4f} "
                  f"gnorm={metrics['grad_norm']:.3f} lr={metrics['lr']:.2e}")

        result = run(
            step, params, opt, loader,
            TrainLoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                            ckpt_dir=args.ckpt_dir, log_every=10),
            metrics_cb=log,
        )
        loader.close()
        hist = result["history"]
        print(f"done: {len(hist)} steps, restarts={result['restarts']}, "
              f"stragglers={result['straggler_events']}, "
              f"final loss={hist[-1].loss:.4f}")


if __name__ == "__main__":
    main()
