"""A configuration and mixes small enough for the CPU, shaped like the cells."""

TINY_CFG = {
    "model_type": "llama", "hidden_act": "silu", "hidden_size": 128,
    "intermediate_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "rope_scaling": None, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "head_dim": 32, "program_arch": "deepseek-coder-33b",
    "reference": "bench/reference/decoder.py",
    "serving": {"hot_pool_bytes": 64 * 4096, "page_size": 4, "max_batch": 4},
    "check": {"requests": 4, "max_logit_gap": 0.15, "max_logprob_gap": 0.15},
}

TINY_MOE_CFG = dict(
    TINY_CFG, model_type="olmoe", num_key_value_heads=4, intermediate_size=64,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=False, qk_norm=True,
    program_arch="olmoe-1b-7b",
    serving={"hot_pool_bytes": 12 * 8192, "page_size": 4, "max_batch": 4},
    # Routing in bfloat16 can pick another expert than float32 does near a tie,
    # which moves a row's log-probabilities by up to about a tenth.
    check={"requests": 4, "max_logit_gap": 0.15, "max_logprob_gap": 0.5})

# The dense configuration through an architecture module that only the tests
# know, added as a file of its own.
RECORDING_CFG = dict(TINY_CFG, reference="bench/tests/recording_arch.py")

# Requests come in bursts of twice `max_batch`, all due at once, so the engine
# runs full batches and queues the rest however fast a step is: the window of
# a 3 s run holds a burst of 8 and one of 4.
OPEN_MIX = {
    "rate_per_s": 4.0, "burst": 8, "preroll_s": 1.0, "drain_s": 3.0,
    "prompt_tokens": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
    "output_tokens": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
}

# More live pages than the MoE configuration's 12 hot slots hold: a burst of 8
# requests of 2 to 4 pages each is preempted, and its pages go through the
# tier and back.
SPILL_MIX = dict(OPEN_MIX,
                 prompt_tokens={"median": 6, "sigma": 0.3, "min": 4, "max": 8},
                 output_tokens={"median": 6, "sigma": 0.3, "min": 4, "max": 8})
