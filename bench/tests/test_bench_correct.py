"""The comparison that decides ``correct``, on the CPU at a tiny size.

A sound run passes; a run with the timed path broken underneath fails, once for
each fault a served cell can have on one chip, and once for logits that are
wrong where the served token is not; the float8 control, put in the program's
place, fails too. The chip check is skipped: ``run_cell`` drives the
rest of a run on whatever device JAX has.

The tiny mixes come in bursts larger than the engine's batch, so that runs
batch several rows and, in the MoE configuration, preempt, whatever a step
costs on this CPU. The tests assert that load: a fault that needs it must fail
the assert, not pass unseen, when the program gets faster.

The faults wrap the decode step without knowing its signature: it returns
the logits first and the state it updates after them."""


import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import peaks, run, system
from bench.reference import decoder, ops
from bench.tests import recording_arch
from bench.tests.tiny import OPEN_MIX, RECORDING_CFG, SPILL_MIX, TINY_CFG, TINY_MOE_CFG

SEED = 2**31 + 99


@pytest.fixture()
def program(tmp_path):
    system.import_program()
    from repro.core import emucxl as ecxl
    import repro.serving.engine as engine_mod

    lib = ecxl.default_instance()
    was = lib._initialized
    decode = engine_mod.paged_decode_step
    yield engine_mod
    engine_mod.paged_decode_step = decode
    if lib._initialized and not was:
        lib.exit()


def _run(cfg, mix, tmp_path, seconds=3.0, per_layer=(), trace=False):
    return run.run_cell(cfg, mix, list(per_layer), SEED, seconds, trace, jax.devices()[0],
                        tmp_path / "trace")


def _state_unchanged(decode):
    """The state handed back as it came in: copies of the arguments taken
    before the call, so that a step that donates its inputs cannot delete
    them. Each state output gets the first argument array not yet used that
    has its shape and type."""
    def step(*args, **kwargs):
        before = [jnp.copy(x) for x in jax.tree.leaves((args, kwargs))
                  if isinstance(x, jax.Array)]
        logits, *state = decode(*args, **kwargs)

        def unchanged(new):
            i = next(i for i, x in enumerate(before)
                     if x.shape == new.shape and x.dtype == new.dtype)
            return before.pop(i)

        return (logits, *jax.tree.map(unchanged, state))
    return step


def _half_batch(decode):
    def step(*args, **kwargs):
        logits, *state = decode(*args, **kwargs)
        b = logits.shape[0]
        half = (b + 1) // 2
        return (jnp.concatenate([logits[:half], logits[:b - half]]), *state)
    return step


def _token_altered(decode):
    def step(*args, **kwargs):
        logits, *state = decode(*args, **kwargs)
        return (jnp.roll(logits, 1, axis=-1), *state)
    return step


def _logits_scaled(decode):
    """Logits a quarter too sharp: every served token is the same, its
    probability is not."""
    def step(*args, **kwargs):
        logits, *state = decode(*args, **kwargs)
        return (logits * 1.25, *state)
    return step


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered, "logits_scaled": _logits_scaled}


@pytest.mark.parametrize("cfg,mix", [(TINY_CFG, OPEN_MIX), (TINY_MOE_CFG, SPILL_MIX)],
                         ids=["dense-open", "moe-spilling"])
def test_sound_run_is_correct(program, tmp_path, cfg, mix):
    res = _run(cfg, mix, tmp_path)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["load"]["batch_max"] >= 2, res["load"]
    if cfg is TINY_MOE_CFG:
        assert res["load"]["preemptions"] > 0, res["load"]


def test_program_rows_are_the_served_tokens_logits(program, tmp_path):
    """The rows kept for each sampled request are the decode call's logits at
    the positions that produced its served tokens: their argmax is the token."""
    res = _run(TINY_CFG, OPEN_MIX, tmp_path)
    for (_, tokens), rows in zip(res["sample"], res["sample_rows"]):
        assert rows.shape == (len(tokens), TINY_CFG["vocab_size"])
        assert rows.argmax(axis=-1).tolist() == tokens


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(program, tmp_path, fault):
    program.paged_decode_step = FAULTS[fault](program.paged_decode_step)
    res = _run(TINY_CFG, OPEN_MIX, tmp_path)
    # A batch of one has no other half: most decode calls have to batch for the
    # fault to reach the sampled requests (a rare pair of rows is not enough).
    assert res["load"]["batch_mean"] >= 2, res["load"]
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("cfg", [TINY_CFG, TINY_MOE_CFG], ids=["dense", "moe"])
def test_control_fails(cfg):
    """The reference in float8 (e4m3), put in the program's place, reads above
    a limit (384 positions; at the cell's size the chip reads the same,
    section 2 of PERF.md)."""
    rng = np.random.default_rng(0)
    requests = [(rng.integers(0, cfg["vocab_size"], 8).tolist(),
                 rng.integers(0, cfg["vocab_size"], 24).tolist()) for _ in range(16)]
    ref = decoder.served_rows(cfg, 1234, requests, seq_len=32, batch=16)
    low = decoder.served_rows(cfg, 1234, requests, seq_len=32, batch=16, quant=ops.fp8)
    numbers = run.compare(ref, low, [r.argmax(axis=-1) for r in low])
    assert any(v > cfg["check"][k] for k, v in numbers.items()), numbers


def test_compare_reads_each_number():
    ref = np.log(np.asarray([[0.5, 0.3, 0.2], [0.1, 0.1, 0.8]]))
    numbers = run.compare([ref], [ref + 7.0], [[0, 2]])     # a shift leaves log-softmax alone
    assert numbers == {"max_logit_gap": 0.0, "max_logprob_gap": pytest.approx(0.0, abs=1e-12)}
    numbers = run.compare([ref], [ref], [[1, 2]])
    assert numbers["max_logit_gap"] == pytest.approx(np.log(0.5 / 0.3))
    assert run.compare([ref], [ref[:1]], [[0, 2]])["max_logprob_gap"] == float("inf")


def test_architecture_joins_as_files(program, tmp_path, monkeypatch):
    """A configuration whose "reference" names a module that only the tests
    know runs a whole cell through it, traced: the harness calls every
    function of the interface, and the run is correct. The CPU has no
    published peaks; the test lends it the v5e's so that the per-layer
    readers run (their numbers are not read)."""
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    recording_arch.CALLED.clear()
    per_layer = [{"name": n, "unit": "%"} for n in ("decode_mfu.fit", "paged_attn_roofline.fit")]
    res = _run(RECORDING_CFG, OPEN_MIX, tmp_path, per_layer=per_layer, trace=True)
    assert res["correct"], res["compared"]
    assert recording_arch.CALLED == set(run.ARCH_INTERFACE)
    assert "decode_mfu.fit" in res["metrics"]
    harness = [p for p in pathlib.Path(run.__file__).parent.rglob("*.py")
               if "tests" not in p.parts]
    assert not [p for p in harness if "recording_arch" in p.read_text()]
