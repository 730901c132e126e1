"""Bring-up check of the main path on a TPU, at DeepSeek-Coder-33B's published widths.

    python chip_smoke.py               # one chip: environment, kernel, tiered serving
    python chip_smoke.py --four-chips  # four chips: the sharded training path only

The one-chip run serves through the engine builder that ``python -m
repro.launch.serve`` uses: continuous batching over the two-tier paged KV cache,
with cold pages demoted to the emulated CXL tier (``pinned_host``) and promoted
back. The four-chip run takes training steps through the builder of
``python -m repro.launch.train`` on a 2x2 mesh under the ``train_fsdp`` rules.
Weights are random, from a seed.

Every check raises on failure. Details go to earlier lines; the last line of
standard output is one JSON object, printed only when every check passed. Without
a TPU the script exits with code 2 and prints no such line.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.metadata
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

MODEL = "deepseek-coder-33b"
SEED = 0

# Depth cut for serving: the deepest that one chip holds beside the KV pool. A
# layer holds 530M parameters, 0.99 GiB in bf16, and the untied embedding and
# unembedding 0.86 GiB: 14.70 GiB for 14 layers by the init's memory_analysis().
# With 12 layers the chip's peak was 13.77 GB of its 16.91 GB limit, so 14 layers
# leave about 1 GB for the float32 pool (8 KiB per token per layer), the decode
# step's buffers and the tier moves, and a 15th would not fit. All 62 published
# layers would need 62 GiB.
SERVE_LAYERS = 14
PAGE_SIZE = 16
PROMPT_LEN = 128
NEW_TOKENS = 32
REQUESTS = 4
# Slots for three requests: the fourth arrives after ARRIVAL_STEPS decode steps and
# preempts a running request, whose pages by then hold real K/V.
ARRIVAL_STEPS = 40

# Depth cut for training. Four layers are 2.6B parameters: 36 GB of bf16 weights,
# float32 master copy and two float32 moments, more than two chips hold. On a 2x2
# mesh the step compiles to 8.4 GiB of state and 4.5 GiB of temporaries per chip.
TRAIN_LAYERS = 4
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 256, 3

# The kernel returns q's dtype, bf16, whose rounding alone is up to 2^-9 of a
# value; everything before it runs in float32, which adds ~1e-6. So each element
# must lie within 2^-8 of the float32 reference, relative, plus 1e-5.
KERNEL_RTOL, KERNEL_ATOL = 2.0 ** -8, 1e-5
# Step 1's loss on the mesh against the same parameters and batch on one chip.
# Both run in bf16 and round different partial sums: on a v5e 2x2 mesh the two
# differed by 9.6e-3 at these sizes. The same comparison in float32 on the CPU
# agrees to 1e-6, so that gap is bf16 rounding and not the partitioning.
LOSS_ATOL = 2e-2


class SmokeFailure(AssertionError):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@contextlib.contextmanager
def timed(label: str, times: dict):
    t = time.perf_counter()
    yield
    times[label] = time.perf_counter() - t
    print(f"[time] {label}: {times[label]} s", flush=True)


# ---------------------------------------------------------------------- phases
def phase_environment(devices) -> None:
    print(f"jax {jax.__version__}, jaxlib {importlib.metadata.version('jaxlib')}, "
          f"libtpu {importlib.metadata.version('libtpu')}")
    for d in devices:
        print(f"device {d.id}: platform={d.platform} kind={d.device_kind!r}")
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}")


def phase_tiers() -> None:
    from repro.core import emucxl as ecxl

    lib = ecxl.default_instance()
    lib.init()
    kinds = {node: lib.memory_kind(node)
             for node in (ecxl.LOCAL_MEMORY, ecxl.REMOTE_MEMORY)}
    print(f"emucxl tiers: local -> {kinds[0]}, remote -> {kinds[1]}; "
          f"hardware model {lib.hw.name}")
    require(kinds == {0: "device", 1: "pinned_host"},
            f"tiers resolved to {kinds}, expected device / pinned_host")


def phase_kernel(cfg, times: dict) -> None:
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref

    N, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, max_pages, P = 4, 10, 64
    rng = np.random.default_rng(SEED)
    q = jnp.asarray(rng.standard_normal((B, N, hd)), jnp.bfloat16)
    k_pages = jnp.asarray(rng.standard_normal((P, PAGE_SIZE, K, hd)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((P, PAGE_SIZE, K, hd)), jnp.float32)
    table = jnp.asarray(rng.permutation(P)[: B * max_pages].reshape(B, max_pages),
                        jnp.int32)
    # One token, a partial page, a partial last page, every page full.
    lengths = jnp.asarray([1, 17, 100, max_pages * PAGE_SIZE], jnp.int32)
    window = jnp.int32(1 << 30)
    scale = hd ** -0.5
    args = (q, k_pages, v_pages, table, lengths, window)
    with timed("kernel first call (compile)", times):
        out = jax.block_until_ready(paged_attention(*args, scale=scale))
    with timed("kernel warm call", times):
        out = jax.block_until_ready(paged_attention(*args, scale=scale))
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_ref(q.astype(jnp.float32), k_pages, v_pages, table,
                                  lengths, window, scale=scale)
    out32, ref = np.asarray(out, np.float32), np.asarray(ref)
    err = np.abs(out32 - ref)
    excess = float(np.max(err - KERNEL_RTOL * np.abs(ref)))
    print(f"kernel vs reference at N={N} K={K} hd={hd} page={PAGE_SIZE}: "
          f"max abs err {err.max():.3e}, max |ref| {np.abs(ref).max():.3e}, "
          f"max err beyond rtol {excess:.3e} (atol {KERNEL_ATOL:.0e})")
    require(out.shape == (B, N, hd) and out.dtype == jnp.bfloat16,
            f"kernel output {out.shape} {out.dtype}")
    require(bool(np.all(np.isfinite(out32))), "kernel output not finite")
    require(excess <= KERNEL_ATOL, "kernel disagrees with the reference")


def phase_serving(cfg, times: dict) -> None:
    from repro.core import emucxl as ecxl
    from repro.launch.serve import build_engine

    pages_per_req = -(-(PROMPT_LEN + NEW_TOKENS) // PAGE_SIZE)
    with timed("engine build (weights + pool)", times):
        eng = build_engine(cfg, num_slots=3 * pages_per_req, page_size=PAGE_SIZE,
                           max_batch=REQUESTS, max_pages_per_seq=pages_per_req,
                           seed=SEED)
        jax.block_until_ready(eng.params)
    pool = eng.pool
    print(f"pool: {pool.num_slots} slots of {pool._page_bytes()} bytes "
          f"({pool.dtype.__name__}), {cfg.num_layers} layers")
    rng = np.random.default_rng(SEED)
    prompts = [list(rng.integers(0, cfg.vocab_size, PROMPT_LEN))
               for _ in range(REQUESTS)]
    for p in prompts[:-1]:
        eng.submit(p, max_new_tokens=NEW_TOKENS)

    t0 = time.perf_counter()
    with timed("first engine step (compile)", times):
        eng.step()
    for _ in range(ARRIVAL_STEPS - 1):
        eng.step()
    # Hot bytes of every page before the arrival, to compare across the tiers.
    before = {key: pool.hot_page_bytes(ref.hot_slot)
              for key, ref in pool._refs.items() if ref.hot_slot is not None}
    eng.submit(prompts[-1], max_new_tokens=NEW_TOKENS)
    eng.step()
    require(eng.preemptions >= 1, "the arrival preempted no request")
    victim = next(r for r in eng.requests.values() if r.state == "preempted")
    key = (victim.rid, 0)                # a full page no later step writes
    cold_ptr = pool._refs[key].cold_ptr
    require(cold_ptr is not None, f"page {key} of the preempted request is not cold")
    demoted = np.asarray(pool.slab.read(cold_ptr, pool._page_bytes()))
    require(np.array_equal(demoted, before[key]),
            f"cold copy of page {key} differs from its hot bytes")
    require(bool(np.any(demoted)), f"page {key} holds no K/V yet")
    lib = pool.session.lib
    cold = [a for a in lib.allocations().values() if a.node == ecxl.REMOTE_MEMORY]
    cold_kinds = {a.data.sharding.memory_kind for a in cold}
    remote_bytes = eng.tier_stats()["remote_bytes"]
    print(f"after the arrival: preemptions={eng.preemptions}, victim request "
          f"{victim.rid} at position {victim.position}, {len(cold)} cold "
          f"buffers in {cold_kinds}, remote_bytes={remote_bytes}")
    require(remote_bytes > 0, "no bytes in the remote tier after a preemption")
    require(cold_kinds == {"pinned_host"}, f"cold tier buffers in {cold_kinds}")

    promoted = False
    while any(r.state != "finished" for r in eng.requests.values()):
        eng.step()
        ref = pool._refs.get(key)
        if not promoted and ref is not None and ref.hot_slot is not None:
            require(np.array_equal(pool.hot_page_bytes(ref.hot_slot), demoted),
                    f"promoted page {key} differs from the bytes demoted")
            promoted = True
            print(f"page {key} promoted back: {demoted.size} bytes equal")
    times["serving (all steps)"] = elapsed = time.perf_counter() - t0
    require(promoted, f"page {key} was never promoted back")

    generated = [r.generated for r in eng.requests.values()]
    n_tokens = sum(len(g) for g in generated)
    require(all(len(g) == NEW_TOKENS for g in generated),
            f"generated lengths {[len(g) for g in generated]}")
    require(all(0 <= t < cfg.vocab_size for g in generated for t in g),
            "a generated token lies outside the vocabulary")
    steady = elapsed - times["first engine step (compile)"]
    print(f"[time] serving (all steps): {elapsed} s")
    print(f"generated {n_tokens} tokens: {n_tokens / elapsed} tokens/s overall, "
          f"{n_tokens / steady} tokens/s after the first step")
    print(f"tier stats: {eng.tier_stats()}")


def _check_quarters(tree, devices, what: str) -> None:
    """Every sharded leaf holds exactly a quarter on each of the four devices."""
    sharded = replicated = 0
    per_device = dict.fromkeys(devices, 0)
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        shards = x.addressable_shards
        require({s.device for s in shards} == set(devices),
                f"{what}{jax.tree_util.keystr(path)} is on "
                f"{sorted(s.device.id for s in shards)}")
        for s in shards:
            per_device[s.device] += s.data.nbytes
        if x.sharding.is_fully_replicated:
            replicated += 1
            continue
        sharded += 1
        require(all(s.data.size * len(devices) == x.size for s in shards),
                f"{what}{jax.tree_util.keystr(path)} shards "
                f"{[s.data.shape for s in shards]} of {x.shape}")
    gib = {d.id: round(b / 2**30, 3) for d, b in per_device.items()}
    print(f"{what}: {sharded} leaves sharded in quarters, {replicated} replicated; "
          f"GiB per device {gib}")


def phase_four_chips(cfg, devices, times: dict) -> None:
    from repro.data.synthetic import SyntheticTokens
    from repro.distributed import axis_rules
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_trainer
    from repro.models import transformer as tf
    from repro.optim import adamw

    rules = "train_fsdp"
    mesh = make_mesh((2, 2), ("data", "model"))
    hp = adamw.OptimizerConfig()
    opts = tf.ModelOptions()
    data = SyntheticTokens(cfg, TRAIN_BATCH, TRAIN_SEQ, seed=SEED)
    losses = []
    with mesh, axis_rules(mesh, rules):
        with timed("trainer build (sharded init)", times):
            step, params, opt = build_trainer(cfg, mesh, rules, hp, opts, seed=SEED)
            jax.block_until_ready((params, opt))
        _check_quarters(params, devices, "params")
        _check_quarters(opt, devices, "optimizer state")
        host_params = jax.device_get(params)
        for i in range(TRAIN_STEPS):
            with timed(f"train step {i + 1}", times):
                params, opt, metrics = step(params, opt, data.batch_at(i))
                losses.append(float(metrics["loss"]))
            print(f"step {i + 1}: loss={losses[-1]:.6f} "
                  f"grad_norm={float(metrics['grad_norm']):.4f}")
        _check_quarters(params, devices, "params after training")
    del params, opt
    one_chip = jax.sharding.SingleDeviceSharding(devices[0])
    ref_params = jax.device_put(host_params, one_chip)
    with timed("one-chip forward (compile + run)", times):
        ref_loss = float(jax.jit(lambda p, b: tf.loss_fn(p, cfg, b, opts)[0])(
            ref_params, jax.device_put(data.batch_at(0), one_chip)))
    print(f"losses on the 2x2 mesh {losses}; step 1 against one chip {ref_loss:.6f}: "
          f"diff {abs(losses[0] - ref_loss):.3e} (atol {LOSS_ATOL:.0e})")
    require(all(np.isfinite(losses)), "a training loss is not finite")
    require(abs(losses[0] - ref_loss) <= LOSS_ATOL,
            "step 1's loss on the mesh disagrees with one chip")


# ---------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training path, on four chips")
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices}", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"{want} chips needed, JAX found {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:want]

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    phase_environment(devices)
    base = get_config(MODEL)
    times: dict = {}
    if args.four_chips:
        cfg = dataclasses.replace(base, num_layers=TRAIN_LAYERS)
        print(f"model {MODEL}: {TRAIN_LAYERS} of {base.num_layers} layers, "
              f"{cfg.param_count() / 1e9:.2f}B parameters")
        phase_four_chips(cfg, devices, times)
    else:
        cfg = dataclasses.replace(base, num_layers=SERVE_LAYERS)
        print(f"model {MODEL}: {SERVE_LAYERS} of {base.num_layers} layers, "
              f"{cfg.param_count() / 1e9:.2f}B parameters")
        phase_tiers()
        phase_kernel(cfg, times)
        phase_serving(cfg, times)
        stats = devices[0].memory_stats() or {}
        print(f"device memory: peak {stats.get('peak_bytes_in_use')} of "
              f"{stats.get('bytes_limit')} bytes")
    print(f"phase times (s): {json.dumps(times)}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
