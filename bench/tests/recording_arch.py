"""An architecture module that only the tests know: the GQA family's
(``bench/reference/decoder.py``), with each interface function recording that
the harness called it. A configuration names it in its ``"reference"`` key
(``tiny.RECORDING_CFG``); no other file of the harness names it."""

from bench.reference import decoder

CALLED: set = set()


def _recorded(name: str):
    fn = getattr(decoder, name)

    def call(*args, **kwargs):
        CALLED.add(name)
        return fn(*args, **kwargs)

    return call


global_leaves = _recorded("global_leaves")
layer_groups = _recorded("layer_groups")
arch_config = _recorded("arch_config")
program_params = _recorded("program_params")
served_rows = _recorded("served_rows")
decode_step_flops = _recorded("decode_step_flops")
paged_attention_cost = _recorded("paged_attention_cost")
