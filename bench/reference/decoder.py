"""The architecture module of pre-norm decoders with GQA attention: what the
benchmark knows of the family, behind the interface that ``bench/run.py``
calls (``ARCH_INTERFACE`` there). A configuration names it in its
``"reference"`` key.

Covers Llama-style blocks (RMSNorm, GQA attention with rotary positions on the
halves of each head, SwiGLU MLP: DeepSeek-Coder) and OLMoE-style blocks (the
same, with RMSNorm on each head's query and key, and a softmax router choosing
``num_experts_per_tok`` of ``num_experts`` SwiGLU experts, their weights not
renormalised). Every layer has the same shape: one layer group, ``layers``.

- ``global_leaves``, ``layer_groups``: the weight leaves (``bench/weights.py``
  draws them from the seed);
- ``arch_config``, ``program_params``: the program's ArchConfig and its
  weights in its layout;
- ``served_rows``: the plain float32 reference, independent of the program.
  It runs layer by layer, so that only one layer's weights are in memory at a
  time, with every matrix product at full float32 precision. ``quant`` rounds
  each product's inputs: the identity for the reference, a lower precision for
  the control;
- ``decode_step_flops``, ``paged_attention_cost``: operations and bytes of one
  decode step, computed from shapes. These are the algorithm's counts, not
  what the program happens to execute: a step that computes every expert of a
  mixture, or fetches pages past a sequence's end, does more than is counted.

``cfg`` is the configuration file as run (Hugging Face keys).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as wts
from bench.reference import ops
from bench.reference.ops import Quant, identity, mm, rms_norm, rope


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


# ------------------------------------------------------------------ the weights
# name -> (shape, kind, fan-in, dtype); kind is "matrix", "embed" or "norm".
def global_leaves(cfg: dict) -> dict:
    D, V, dt = cfg["hidden_size"], wts.padded_vocab(cfg), wts.served_dtype(cfg)
    return {
        "embed": ((V, D), "embed", 1, dt),
        "final_norm": ((D,), "norm", 1, dt),
        "unembed": ((D, V), "matrix", D, dt),
    }


def layer_leaves(cfg: dict) -> dict:
    D, hd, dt = cfg["hidden_size"], head_dim(cfg), wts.served_dtype(cfg)
    N, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    F = cfg["intermediate_size"]
    leaves = {
        "ln1": ((D,), "norm", 1, dt),
        "wq": ((D, N, hd), "matrix", D, dt),
        "wk": ((D, K, hd), "matrix", D, dt),
        "wv": ((D, K, hd), "matrix", D, dt),
        "wo": ((N, hd, D), "matrix", N * hd, dt),
        "ln2": ((D,), "norm", 1, dt),
    }
    if cfg.get("qk_norm"):
        leaves["q_norm"] = ((hd,), "norm", 1, dt)
        leaves["k_norm"] = ((hd,), "norm", 1, dt)
    if cfg.get("num_experts"):
        E = cfg["num_experts"]
        # The program holds its router in float32 whatever the served type.
        leaves["router"] = ((D, E), "matrix", D, jnp.float32)
        leaves["w_gate"] = ((E, D, F), "matrix", D, dt)
        leaves["w_up"] = ((E, D, F), "matrix", D, dt)
        leaves["w_down"] = ((E, F, D), "matrix", F, dt)
    else:
        leaves["w_gate"] = ((D, F), "matrix", D, dt)
        leaves["w_up"] = ((D, F), "matrix", D, dt)
        leaves["w_down"] = ((F, D), "matrix", F, dt)
    return leaves


def layer_groups(cfg: dict) -> dict:
    return {"layers": (layer_leaves(cfg), cfg["num_hidden_layers"])}


# ------------------------------------------------------------------ the program
def arch_config(cfg: dict, get_config):
    """The program's ArchConfig for the configuration file: the architecture
    ``program_arch`` names (looked up with the program's `get_config`), with
    every size the file states set from the file."""
    fields = dict(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=head_dim(cfg), vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), qk_norm=bool(cfg.get("qk_norm")),
        d_ff=cfg["intermediate_size"], param_dtype=cfg["torch_dtype"],
        dtype=cfg["torch_dtype"], tie_embeddings=bool(cfg["tie_word_embeddings"]),
    )
    if cfg.get("num_experts"):
        fields.update(moe=True, num_experts=cfg["num_experts"],
                      experts_per_token=cfg["num_experts_per_tok"],
                      moe_d_ff=cfg["intermediate_size"],
                      moe_renormalize=bool(cfg["norm_topk_prob"]))
    return dataclasses.replace(get_config(cfg["program_arch"]), **fields)


def program_params(cfg: dict, key) -> dict:
    """The weights in the program's layout and served type. Call under jit."""
    w = wts.make_served(key, global_leaves(cfg), layer_groups(cfg))
    g, l = w["global"], w["layers"]
    attn = {n: l[n] for n in ("wq", "wk", "wv", "wo", "q_norm", "k_norm") if n in l}
    ffn = {n: l[n] for n in ("router", "w_gate", "w_up", "w_down") if n in l}
    stack = {"ln1": l["ln1"], "ln2": l["ln2"], "attn": attn,
             ("moe" if cfg.get("num_experts") else "mlp"): ffn}
    return {"embed": g["embed"], "stack": stack, "final_norm": g["final_norm"],
            "unembed": g["unembed"]}


# ------------------------------------------------------------------ the reference
def _swiglu(quant, x, wg, wu, wd):
    h = jax.nn.silu(mm(quant, "td,df->tf", x, wg, -1, 0)) * mm(
        quant, "td,df->tf", x, wu, -1, 0)
    return mm(quant, "tf,fd->td", h, wd, -1, 0)


def block(cfg: dict, quant: Quant, h: jax.Array, w: dict) -> jax.Array:
    """One decoder layer over h (B, S, D), causal."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, S, D = h.shape
    N, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = rms_norm(h, w["ln1"], eps)
    q = mm(quant, "bsd,dnh->bsnh", x, w["wq"], -1, 0)
    k = mm(quant, "bsd,dkh->bskh", x, w["wk"], -1, 0)
    v = mm(quant, "bsd,dkh->bskh", x, w["wv"], -1, 0)
    if cfg.get("qk_norm"):
        q = rms_norm(q, w["q_norm"], eps)
        k = rms_norm(k, w["k_norm"], eps)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    k = jnp.repeat(k, N // K, axis=2)
    v = jnp.repeat(v, N // K, axis=2)
    s = mm(quant, "bsnh,btnh->bnst", q, k, -1, -1) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm(quant, "bnst,btnh->bsnh", jax.nn.softmax(s, axis=-1), v, -1, 1)
    h = h + mm(quant, "bsnh,nhd->bsd", o, w["wo"], -1, 0)
    x = rms_norm(h, w["ln2"], eps).reshape(B * S, D)
    if cfg.get("num_experts"):
        probs = jax.nn.softmax(mm(quant, "td,de->te", x, w["router"], -1, 0), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
        if cfg.get("norm_topk_prob"):
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        combine = jnp.zeros_like(probs).at[
            jnp.arange(B * S)[:, None], top_e].set(top_p)            # (T, E)

        def expert(acc, e):
            y = _swiglu(quant, x, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
            return acc + combine[:, e, None] * y, None

        f, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(cfg["num_experts"]))
    else:
        f = _swiglu(quant, x, w["w_gate"], w["w_up"], w["w_down"])
    return h + f.reshape(B, S, D)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, quant: Quant):
    cfg = dict(cfg_items)
    g_table, l_table = global_leaves(cfg), layer_leaves(cfg)

    @jax.jit
    def embed(key, tokens):
        return wts.global_weights(key, g_table)["embed"][tokens]

    @jax.jit
    def layer(key, l, h):
        return block(cfg, quant, h, wts.layer_weights(key, "layers", l_table, l))

    @jax.jit
    def head(key, h):
        g = wts.global_weights(key, g_table)
        x = rms_norm(h, g["final_norm"], cfg["rms_norm_eps"])
        return mm(quant, "bsd,dv->bsv", x, g["unembed"][:, : cfg["vocab_size"]], -1, 0)

    return embed, layer, head


def logits(cfg: dict, seed: int, tokens: np.ndarray, quant: Quant = identity) -> np.ndarray:
    """Logits (B, S, vocab) of every position of `tokens` (B, S), float32."""
    embed, layer, head = _programs(tuple(sorted(
        (k, v) for k, v in cfg.items() if isinstance(v, (int, float, str, bool)))), quant)
    key = wts.seed_key(seed)
    h = embed(key, jnp.asarray(tokens, jnp.int32))
    for l in range(cfg["num_hidden_layers"]):
        h = layer(key, jnp.int32(l), h)
    return np.asarray(head(key, h))


def served_rows(cfg: dict, seed: int, requests: Sequence[tuple], seq_len: int,
                batch: int, quant: Quant = identity) -> List[np.ndarray]:
    """For each (prompt, served tokens) pair, the logits (served, vocab) at the
    positions that produced the served tokens."""
    return ops.served_rows(lambda tokens: logits(cfg, seed, tokens, quant),
                           requests, seq_len, batch)


# ------------------------------------------------------------------ the counts
def matmul_params_per_token(cfg: dict) -> int:
    """Weights each decoded token multiplies: attention projections, the feed-
    forward (routed experts: only those chosen, and the router) and the output
    head. The embedding is a lookup and is not counted."""
    D, hd = cfg["hidden_size"], head_dim(cfg)
    N, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = D * N * hd + 2 * D * K * hd + N * hd * D
    if cfg.get("num_experts"):
        ffn = (cfg["num_experts_per_tok"] * 3 * D * cfg["intermediate_size"]
               + D * cfg["num_experts"])
    else:
        ffn = 3 * D * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + ffn) + D * cfg["vocab_size"]


def attention_flops(cfg: dict, context: int) -> int:
    """Scores and weighted values of one query token over `context` tokens,
    every layer."""
    return (cfg["num_hidden_layers"] * 2 * 2 * cfg["num_attention_heads"]
            * head_dim(cfg) * context)


def decode_step_flops(cfg: dict, contexts: Iterable[int]) -> int:
    """Model FLOPs of one decode step: one token for each sequence of the batch,
    `contexts` holding each one's length including the new token."""
    contexts = list(contexts)
    return (2 * matmul_params_per_token(cfg) * len(contexts)
            + sum(attention_flops(cfg, c) for c in contexts))


def paged_attention_cost(cfg: dict, contexts: Iterable[int], page_size: int,
                         kv_bytes: int = 4, q_bytes: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of the paged-attention kernel over all layers of one step,
    counting live pages only: the pages that hold some of a sequence's
    `context` tokens, with K and V of every kv head, plus the query read and
    the output written."""
    L, hd = cfg["num_hidden_layers"], head_dim(cfg)
    N, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    flops = nbytes = 0
    for c in contexts:
        live_pages = -(-c // page_size)
        flops += L * 2 * 2 * N * hd * c
        nbytes += L * (2 * live_pages * page_size * K * hd * kv_bytes
                       + 2 * N * hd * q_bytes)
    return flops, nbytes
