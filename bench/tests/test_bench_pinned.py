"""The yardstick's numbers for the two tiny configurations, pinned: a digest of
every program leaf, the reference's logit rows for a fixed block of tokens,
and one decode step's FLOPs and paged-attention (FLOPs, bytes). For the cell
``dscoder33b-10l.chat-fit``, the shapes of the program's weights and a digest
of the schedule its mix offers.

The values were taken, with this file's own code, from the tree before the
architecture of a configuration moved behind its architecture module
(``bench/reference/decoder.py``); the move changed none of them. Weights,
reference and counts of a configuration may change only with a new
configuration, never under an existing one."""

import functools
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import run, system, traffic, weights
from bench.tests.tiny import TINY_CFG, TINY_MOE_CFG

SEED = 2**31 + 7
CONTEXTS = [3, 9, 17]

LEAVES = {
    "dense": {
        "['embed']": "86bfdc9ca9fa8365 bfloat16 (512, 128)",
        "['final_norm']": "04b20cc18a7fc00b bfloat16 (128,)",
        "['stack']['attn']['wk']": "9a1d3a2bd59fed6e bfloat16 (2, 128, 2, 32)",
        "['stack']['attn']['wo']": "2570ef27d4faf691 bfloat16 (2, 4, 32, 128)",
        "['stack']['attn']['wq']": "15c264b3a590677e bfloat16 (2, 128, 4, 32)",
        "['stack']['attn']['wv']": "ce2a2c315ce25249 bfloat16 (2, 128, 2, 32)",
        "['stack']['ln1']": "f91c71ffb31d544a bfloat16 (2, 128)",
        "['stack']['ln2']": "e67bdd7da0e3929c bfloat16 (2, 128)",
        "['stack']['mlp']['w_down']": "504e71ba93316a80 bfloat16 (2, 256, 128)",
        "['stack']['mlp']['w_gate']": "7eccfdb3005fbc72 bfloat16 (2, 128, 256)",
        "['stack']['mlp']['w_up']": "a1e40cd03ebe879a bfloat16 (2, 128, 256)",
        "['unembed']": "380b64bcace6c236 bfloat16 (128, 512)",
    },
    "moe": {
        "['embed']": "86bfdc9ca9fa8365 bfloat16 (512, 128)",
        "['final_norm']": "04b20cc18a7fc00b bfloat16 (128,)",
        "['stack']['attn']['k_norm']": "1aacb2d5763ba388 bfloat16 (2, 32)",
        "['stack']['attn']['q_norm']": "61df877cb3f5b30f bfloat16 (2, 32)",
        "['stack']['attn']['wk']": "3537c12b4d2c0efb bfloat16 (2, 128, 4, 32)",
        "['stack']['attn']['wo']": "2570ef27d4faf691 bfloat16 (2, 4, 32, 128)",
        "['stack']['attn']['wq']": "15c264b3a590677e bfloat16 (2, 128, 4, 32)",
        "['stack']['attn']['wv']": "c02ff7e4415a2756 bfloat16 (2, 128, 4, 32)",
        "['stack']['ln1']": "f91c71ffb31d544a bfloat16 (2, 128)",
        "['stack']['ln2']": "e67bdd7da0e3929c bfloat16 (2, 128)",
        "['stack']['moe']['router']": "554af17e4150f3d1 float32 (2, 128, 8)",
        "['stack']['moe']['w_down']": "9e8305f4855c6e27 bfloat16 (2, 8, 64, 128)",
        "['stack']['moe']['w_gate']": "d49f033c47357212 bfloat16 (2, 8, 128, 64)",
        "['stack']['moe']['w_up']": "4543e2bed84bcf4f bfloat16 (2, 8, 128, 64)",
        "['unembed']": "380b64bcace6c236 bfloat16 (128, 512)",
    },
}
ROWS = {"dense": "d55953d20f5e0a6260fa482d86fd3e0b635fd46069162239f22948632f5e349e",
        "moe": "6de4a05d0473c5df370f43b9158b3fab715b8c1b97788abd39b9fe9e52354db6"}
# decode_step_flops(CONTEXTS), paged_attention_cost(CONTEXTS, page 4)
COUNTS = {"dense": (2_192_384, (29_696, 39_936)), "moe": (1_811_456, (29_696, 76_800))}
CONFIGS = {"dense": TINY_CFG, "moe": TINY_MOE_CFG}


def _digest(x) -> str:
    a = np.asarray(x)
    return f"{hashlib.sha256(a.tobytes()).hexdigest()[:16]} {a.dtype} {a.shape}"


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_program_leaves_are_pinned(name):
    cfg = CONFIGS[name]
    system.import_program()
    params = system.program_params(cfg, SEED, run.architecture(cfg))
    got = {jax.tree_util.keystr(k): _digest(v)
           for k, v in jax.tree_util.tree_leaves_with_path(params)}
    assert got == LEAVES[name]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_rows_are_pinned(name):
    cfg = CONFIGS[name]
    rng = np.random.default_rng(5)
    pairs = [(rng.integers(0, cfg["vocab_size"], 5).tolist(),
              rng.integers(0, cfg["vocab_size"], 7).tolist()) for _ in range(3)]
    rows = run.architecture(cfg).served_rows(cfg, SEED, pairs, seq_len=16, batch=2)
    assert [r.shape for r in rows] == [(7, cfg["vocab_size"])] * 3
    got = hashlib.sha256(b"".join(np.asarray(r, np.float32).tobytes() for r in rows))
    assert got.hexdigest() == ROWS[name]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_are_pinned(name):
    cfg = CONFIGS[name]
    family = run.architecture(cfg)
    page = cfg["serving"]["page_size"]
    got = (family.decode_step_flops(cfg, CONTEXTS),
           family.paged_attention_cost(cfg, CONTEXTS, page))
    assert got == COUNTS[name]


CHAT_FIT_LEAVES = {
    "['embed']": "bfloat16 (32256, 7168)",
    "['final_norm']": "bfloat16 (7168,)",
    "['stack']['attn']['wk']": "bfloat16 (10, 7168, 8, 128)",
    "['stack']['attn']['wo']": "bfloat16 (10, 56, 128, 7168)",
    "['stack']['attn']['wq']": "bfloat16 (10, 7168, 56, 128)",
    "['stack']['attn']['wv']": "bfloat16 (10, 7168, 8, 128)",
    "['stack']['ln1']": "bfloat16 (10, 7168)",
    "['stack']['ln2']": "bfloat16 (10, 7168)",
    "['stack']['mlp']['w_down']": "bfloat16 (10, 19200, 7168)",
    "['stack']['mlp']['w_gate']": "bfloat16 (10, 7168, 19200)",
    "['stack']['mlp']['w_up']": "bfloat16 (10, 7168, 19200)",
    "['unembed']": "bfloat16 (7168, 32256)",
}
CHAT_FIT_SCHEDULE = (249, "62e9ccdba1b981d173ad904abdbf6ad8729be376111a6fa08884673fae282805")


def test_chat_fit_weights_keep_their_shapes():
    cfg = run.load_config("dscoder33b-10l")
    family = run.architecture(cfg)
    out = jax.eval_shape(functools.partial(family.program_params, cfg), weights.seed_key(1))
    assert {jax.tree_util.keystr(k): f"{v.dtype} {v.shape}"
            for k, v in jax.tree_util.tree_leaves_with_path(out)} == CHAT_FIT_LEAVES


def test_chat_fit_schedule_is_pinned():
    s = traffic.schedule(traffic.load_mix("chat-fit"), SEED, 32256, 51)
    key = json.dumps([(a.due_s, a.prompt, a.max_new_tokens) for a in s])
    assert (len(s), hashlib.sha256(key.encode()).hexdigest()) == CHAT_FIT_SCHEDULE


def test_groups_draw_apart():
    """A leaf named alike in two layer groups draws other values in each; the
    reference's float32 view of a layer is the served layer's."""
    table = {"ln1": ((8,), "norm", 1, np.float32), "w": ((8, 4), "matrix", 8, np.float32)}
    key = weights.seed_key(SEED)
    served = weights.make_served(key, {}, {"layers": (table, 2), "dense": (table, 2)})
    for n in table:
        assert not np.array_equal(served["layers"][n], served["dense"][n])
    for group in ("layers", "dense"):
        ref = weights.layer_weights(key, group, table, 1)
        np.testing.assert_array_equal(ref["w"], served[group]["w"][1])
        np.testing.assert_array_equal(ref["ln1"], 1.0 + served[group]["ln1"][1])
