"""Serving integration: paged decode equivalence, engine with preemption + tiering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import emucxl as ecxl
from repro.core.policy import Policy1, Policy2
from repro.models import transformer as tf
from repro.serving.engine import ServingEngine
from repro.serving.kv_manager import PagedKVPool
from repro.serving.paged_decode import paged_decode_step


@pytest.fixture(scope="module")
def model():
    cfg = get_config("gemma3-1b").reduced()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_paged_decode_matches_dense(model):
    cfg, params = model
    B, page, maxp = 2, 8, 4
    state = tf.init_decode_state(params, cfg, B, page * maxp)
    k_pool = jnp.zeros((cfg.num_layers, 16, page, cfg.num_kv_heads,
                        cfg.resolved_head_dim), jnp.float32)
    v_pool = jnp.zeros_like(k_pool)
    table = jnp.asarray(np.stack([np.arange(maxp), np.arange(maxp) + maxp]),
                        jnp.int32)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (B, 12))
    for t in range(12):
        tok = jnp.asarray(toks[:, t : t + 1], jnp.int32)
        dense_logits, state = tf.decode_step(params, cfg, state, tok)
        lengths = jnp.full((B,), t, jnp.int32)
        paged_logits, k_pool, v_pool = paged_decode_step(
            params, cfg, k_pool, v_pool, table, lengths, tok
        )
        np.testing.assert_allclose(dense_logits, paged_logits, atol=1e-3)


def test_decode_step_traces_once_per_batch_size(model):
    """The engine's decode step is traced once per batch size: more steps at a
    size reuse the compiled program, a new size traces one more."""
    cfg, params = model
    # 11 slots: a pool shape no other test decodes against, so the first step
    # at each batch size here is a fresh trace.
    eng = ServingEngine(params, cfg, num_slots=11, page_size=8, max_batch=2,
                        max_pages_per_seq=2, policy=Policy1())

    def traces_after_steps(batch, steps):
        rids = [eng.submit([1, 2], max_new_tokens=steps) for _ in range(batch)]
        before = eng.tier_stats()["decode_traces"]
        eng.step()
        first = eng.tier_stats()["decode_traces"] - before
        for _ in range(steps):
            eng.step()
        assert all(eng.requests[r].state == "finished" for r in rids)
        return first, eng.tier_stats()["decode_traces"] - before

    assert traces_after_steps(1, 4) == (1, 1)
    assert traces_after_steps(2, 4) == (1, 1)
    assert traces_after_steps(1, 2) == (0, 0)


def test_pool_demote_promote_roundtrip(lib):
    pool = PagedKVPool(2, 4, 8, 2, 16, lib=lib)
    pool.alloc_page(0, 0)
    ref_k = np.random.default_rng(0).standard_normal((2, 8, 2, 16)).astype(np.float32)
    slot = pool.hot_table(0, 1)[0]
    pool.k_pool = pool.k_pool.at[:, slot].set(jnp.asarray(ref_k))
    pool.demote(0, 0)
    assert pool.residency(0) == (0, 1)
    assert lib.stats(1) > 0                     # bytes really moved to remote tier
    pool.promote(0, 0)
    assert pool.residency(0) == (1, 0)
    new_slot = pool.hot_table(0, 1)[0]
    np.testing.assert_allclose(np.asarray(pool.k_pool[:, new_slot]), ref_k,
                               atol=1e-6)


def test_pool_policy2_reads_stay_remote(lib):
    pool = PagedKVPool(1, 4, 8, 2, 16, lib=lib, policy=Policy2())
    pool.alloc_page(0, 0)
    pool.demote(0, 0)
    for _ in range(3):
        assert pool.touch(0, 0) is None         # served remote, no promotion
    assert pool.stats.remote_hits == 3
    assert pool.residency(0) == (0, 1)


def test_pool_eviction_on_promote_pressure(lib):
    pool = PagedKVPool(1, 2, 8, 2, 16, lib=lib)   # only 2 hot slots
    pool.alloc_page(0, 0)
    pool.alloc_page(1, 0)
    pool.demote(0, 0)
    pool.alloc_page(2, 0)                         # fills the freed slot
    pool.promote(0, 0)                            # must evict the LRU page
    hot = sum(pool.residency(s)[0] for s in (0, 1, 2))
    cold = sum(pool.residency(s)[1] for s in (0, 1, 2))
    assert hot == 2 and cold == 1


def test_engine_generates_and_preempts(model):
    cfg, params = model
    lib = ecxl.EmuCXL()
    lib.init(local_capacity=1 << 26, remote_capacity=1 << 28)
    eng = ServingEngine(params, cfg, num_slots=4, page_size=8, max_batch=2,
                        max_pages_per_seq=2, policy=Policy1())
    eng.pool.lib = lib
    eng.pool.slab.lib = lib
    rng = np.random.default_rng(5)
    for _ in range(3):                     # 3 x 2 pages needed > 4 slots
        eng.submit(list(rng.integers(0, cfg.vocab_size, 5)), max_new_tokens=6)
    out = eng.run(max_steps=200)
    assert all(len(v) == 6 for v in out.values())
    stats = eng.tier_stats()
    assert eng.preemptions > 0             # pressure forced real demotions
    assert stats["remote_hits"] + stats["local_hits"] > 0
    lib.exit()


def test_engine_imports_shared_prefix(model):
    """Two hosts' engines share one coherent prefix segment: admitted prompts
    skip prefilling the prefix tokens, and the pool holds ONE prefix copy."""
    from repro.core.api import CXLSession
    from repro.core.fabric import Fabric
    from repro.serving.kv_manager import SharedPrefixKV

    cfg, params = model
    page = 8
    with CXLSession(1 << 26, 1 << 28, num_hosts=2,
                    fabric=Fabric(num_hosts=2, pool_ports=1)) as sess:
        shared = SharedPrefixKV(
            sess, num_layers=cfg.num_layers, num_pages=1, page_size=page,
            kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
            home_host=0)
        engines = [
            ServingEngine(params, cfg, num_slots=6, page_size=page, max_batch=1,
                          max_pages_per_seq=3, policy=Policy1(), host=h,
                          session=sess, shared_prefix=shared)
            for h in range(2)
        ]
        # host 0 prefills the prefix once and publishes it
        rng = np.random.default_rng(3)
        prefix = list(rng.integers(0, cfg.vocab_size, page))
        pub_pool = engines[0].pool
        for p in range(1):
            pub_pool.alloc_page(99, p)
        shared.publish(pub_pool, seq_id=99, token_ids=prefix)
        pub_pool.free_sequence(99)
        # both engines serve prompts that start with the shared prefix
        for eng in engines:
            eng.submit(prefix + list(rng.integers(0, cfg.vocab_size, 3)),
                       max_new_tokens=4)
            out = eng.run(max_steps=50)
            assert all(len(v) == 4 for v in out.values())
            assert eng.tier_stats()["prefix_imports"] == 1
        # a long prompt whose tokens DIFFER from the prefix must prefill
        # normally — importing would attend to the wrong KV
        other = [(t + 1) % cfg.vocab_size for t in prefix]
        engines[1].submit([*other, 1, 2], max_new_tokens=2)
        engines[1].run(max_steps=50)
        assert engines[1].tier_stats()["prefix_imports"] == 1  # unchanged
        # requests began decoding after the prefix (import replaced prefill)
        assert all(r.position >= page for e in engines
                   for r in e.requests.values())
        coh = sess.coherence_stats()["total"]
        assert coh["read_misses"] >= 1          # the imports fetched pages
        assert sess.fabric_stats()["pool0"]["bytes_carried"] > 0


def test_engine_policy_comparison(model):
    """Policy1 yields a higher local-hit fraction than Policy2 under reuse."""
    cfg, params = model

    def run_policy(policy):
        lib = ecxl.EmuCXL()
        lib.init(local_capacity=1 << 26, remote_capacity=1 << 28)
        eng = ServingEngine(params, cfg, num_slots=4, page_size=8, max_batch=1,
                            max_pages_per_seq=2, policy=policy)
        eng.pool.lib = lib
        eng.pool.slab.lib = lib
        rng = np.random.default_rng(7)
        for _ in range(3):
            eng.submit(list(rng.integers(0, cfg.vocab_size, 5)), max_new_tokens=5)
        eng.run(max_steps=200)
        pct = eng.pool.stats.percent_local
        lib.exit()
        return pct

    assert run_policy(Policy1()) >= run_policy(Policy2())
