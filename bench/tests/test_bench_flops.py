"""The GQA family's counts (bench/reference/decoder.py) against counts worked
out by hand for one decode step of the configuration and of a mixture of
experts: two sequences, of 10 and 20 tokens with the new one, page 16."""

import json
import pathlib

from bench.reference import decoder as family

CONFIGS = pathlib.Path(family.__file__).parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_dscoder_decode_step():
    cfg = _cfg("dscoder33b-10l")
    # per layer: q 7168*56*128 + k,v 2*7168*8*128 + o 56*128*7168 = 117,440,512
    #            SwiGLU 3*7168*19200 = 412,876,800
    # 10 layers: 5,303,173,120; output head 7168*32256 = 231,211,008
    assert family.matmul_params_per_token(cfg) == 5_534_384_128
    # 2 tokens * 2 FLOPs * params + attention 10 layers * 4 * 56 * 128 * (10 + 20)
    assert family.decode_step_flops(cfg, [10, 20]) == 22_137_536_512 + 8_601_600
    # live pages 1 and 2: per layer 2*pages*16*8*128*4 bytes of K and V, plus
    # 2*56*128*2 bytes of query and output per sequence
    # = (131,072 + 28,672) + (262,144 + 28,672) = 450,560 per layer
    assert family.paged_attention_cost(cfg, [10, 20], 16) == (8_601_600, 4_505_600)


# OLMoE-1B-7B's published sizes (huggingface.co/allenai/OLMoE-1B-7B-0924), for
# the routed-expert count: no cell runs it yet.
OLMOE = {"hidden_size": 2048, "intermediate_size": 1024, "num_attention_heads": 16,
         "num_key_value_heads": 16, "num_hidden_layers": 16, "vocab_size": 50304,
         "num_experts": 64, "num_experts_per_tok": 8}


def test_olmoe_decode_step():
    cfg = OLMOE
    # per layer: q,k,v,o 4*2048*16*128 = 16,777,216; 8 of 64 experts
    # 8*3*2048*1024 = 50,331,648, router 2048*64 = 131,072
    # 16 layers: 1,075,838,976; output head 2048*50304 = 103,022,592
    assert family.matmul_params_per_token(cfg) == 1_178_861_568
    # attention 16 layers * 4 * 16 * 128 * 30 = 3,932,160
    assert family.decode_step_flops(cfg, [10, 20]) == 4 * 1_178_861_568 + 3_932_160
    # per layer (2*1*16*16*128*4 + 2*16*128*2) + (2*2*16*16*128*4 + 2*16*128*2)
    # = (262,144 + 8,192) + (524,288 + 8,192) = 802,816; 16 layers 12,845,056
    assert family.paged_attention_cost(cfg, [10, 20], 16) == (3_932_160, 12_845_056)
