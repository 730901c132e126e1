"""Sharding-rule resolution unit tests + an 8-device distributed train step
(subprocess, because the forced device count must precede jax initialization)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.sharding import RULE_SETS, logical_to_spec


class FakeMesh:
    """Duck-typed mesh: axis_names + shape dict (enough for rule resolution)."""

    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = FakeMesh(data=16, model=16)
MESH3 = FakeMesh(pod=2, data=16, model=16)


def spec(logical, rules="train_fsdp", mesh=MESH, shape=None):
    return tuple(logical_to_spec(logical, RULE_SETS[rules], mesh, shape))


def test_basic_resolution():
    assert spec(("batch", "seq", "embed")) == ("data",)
    assert spec(("layers", "fsdp", "heads", None),
                shape=(16, 1024, 32, 128)) == (None, "data", "model")


def test_missing_mesh_axes_dropped():
    # "pod" is absent from the 2D mesh; batch=(pod, data) resolves to data only
    assert spec(("batch",), mesh=MESH) == ("data",)
    assert spec(("batch",), mesh=MESH3, shape=(256,)) == (("pod", "data"),)


def test_divisibility_fallback():
    # 8 kv heads cannot shard 16 ways -> replicated
    assert spec(("layers", "fsdp", "kv_heads", None),
                shape=(60, 1024, 8, 128)) == (None, "data")
    # 56 q heads likewise
    assert spec(("batch", None, "heads", None),
                shape=(16, 4096, 56, 128)) == ("data",)


def test_priority_heads_over_seq_attn():
    # heads divisible: heads take model; seq_attn yields
    assert spec(("batch", "seq_attn", "heads", None),
                shape=(16, 4096, 32, 128)) == ("data", None, "model")
    # heads NOT divisible: seq_attn claims model (context-parallel q)
    assert spec(("batch", "seq_attn", "heads", None),
                shape=(16, 4096, 56, 128)) == ("data", "model")


def test_cache_seq_yields_to_kv_heads():
    kv_ax = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
    # K=16 divisible: heads shard, cache seq stays whole
    assert spec(kv_ax, rules="serve_tp", shape=(16, 128, 32768, 16, 64)) == \
        (None, "data", None, "model")
    # K=8 not divisible: cache seq takes model
    assert spec(kv_ax, rules="serve_tp", shape=(60, 128, 32768, 8, 128)) == \
        (None, "data", "model")


def test_no_axis_used_twice():
    s = spec(("batch", "fsdp", "heads"), rules="train_fsdp",
             shape=(256, 4096, 16))
    flat = []
    for e in s:
        if isinstance(e, tuple):
            flat += list(e)
        elif e:
            flat.append(e)
    assert len(flat) == len(set(flat))


def test_zero1_shards_fsdp_dim_across_all_axes():
    s = spec(("fsdp", None), rules="train_zero1", mesh=MESH3, shape=(1024, 64))
    assert s == (("pod", "data", "model"),)


_DISTRIBUTED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.distributed import axis_rules
    from repro.launch.mesh import make_mesh
    from repro.launch import specs as sp
    from repro.launch.steps import make_train_step
    from repro.models import transformer as tf
    from repro.optim import adamw
    from repro.data.synthetic import SyntheticTokens

    cfg = get_config("olmoe-1b-7b").reduced()   # exercises MoE EP shard_map
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = "train_fsdp"
    hp = adamw.OptimizerConfig(learning_rate=5e-3, warmup_steps=2)
    with mesh, axis_rules(mesh, rules):
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw.init_state(params, hp)
        p_sh = sp.param_shardings(cfg, mesh, rules)
        o_sh = sp.opt_state_shardings(cfg, hp, mesh, rules)
        params = jax.tree.map(jax.device_put, params, p_sh)
        opt = jax.tree.map(jax.device_put, opt, o_sh)
        src = SyntheticTokens(cfg, batch=8, seq_len=32, seed=0)
        b0 = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}
        # EP (shard_map + ragged_dot) must match the dense-MoE oracle
        step_dense = jax.jit(
            make_train_step(cfg, tf.ModelOptions(moe_impl="dense"), hp),
            in_shardings=(p_sh, o_sh, None), out_shardings=(p_sh, o_sh, None))
        l_dense = float(step_dense(params, opt, b0)[2]["loss"])
        step = jax.jit(make_train_step(cfg, tf.ModelOptions(moe_impl="ep"), hp),
                       in_shardings=(p_sh, o_sh, None),
                       out_shardings=(p_sh, o_sh, None))
        losses = []
        for i in range(30):
            batch = {k: jnp.asarray(v) for k, v in src.batch_at(i).items()}
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
        print(json.dumps({"losses": losses, "l_dense": l_dense,
                          "n_dev": jax.device_count()}))
""")


@pytest.mark.slow
def test_distributed_train_step_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _DISTRIBUTED_SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["n_dev"] == 8
    losses = result["losses"]
    assert all(l == l for l in losses)          # finite
    # EP matches the dense-MoE oracle (capacity drops allow a small gap)
    assert abs(losses[0] - result["l_dense"]) < 0.05
    # learning under EP + FSDP (noisy MoE smoke config: compare window means)
    assert sum(losses[-5:]) / 5 < sum(losses[:5]) / 5 - 0.1


_BUILD_TRAINER_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    from repro.configs import get_config
    from repro.data.synthetic import SyntheticTokens
    from repro.distributed import axis_rules
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_trainer
    from repro.models import transformer as tf
    from repro.optim import adamw

    cfg = get_config("deepseek-coder-33b").reduced()      # float32
    mesh = make_mesh((2, 2), ("data", "model"))
    opts = tf.ModelOptions()
    batch = SyntheticTokens(cfg, batch=8, seq_len=32, seed=0).batch_at(0)
    with mesh, axis_rules(mesh, "train_fsdp"):
        step, params, opt = build_trainer(cfg, mesh, "train_fsdp",
                                          adamw.OptimizerConfig(), opts)
        leaves = jax.tree.leaves((params, opt))
        quarters = all(
            len({s.device for s in x.addressable_shards}) == 4
            and (x.sharding.is_fully_replicated
                 or all(4 * s.data.size == x.size for s in x.addressable_shards))
            for x in leaves)
        n_sharded = sum(not x.sharding.is_fully_replicated for x in leaves)
        host = jax.device_get(params)
        params, opt, m = step(params, opt, batch)
    one = jax.jit(lambda p, b: tf.loss_fn(p, cfg, b, opts)[0])(host, batch)
    print(json.dumps({"quarters": quarters, "n_sharded": n_sharded,
                      "mesh_loss": float(m["loss"]), "one_loss": float(one)}))
""")


def test_build_trainer_shards_state_and_matches_one_device():
    """Parameters and optimizer state are born sharded in quarters over a 2x2
    mesh, and step 1's loss equals one device's forward (float32)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _BUILD_TRAINER_SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["quarters"] and result["n_sharded"] > 0
    assert abs(result["mesh_loss"] - result["one_loss"]) < 1e-5
