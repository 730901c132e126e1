"""The system under test as the benchmark drives it: the program's serving
engine, its decode step and its tier moves. This is the only module of the
benchmark that imports the program (``src/repro``).

``ServedLogits`` keeps, in every run, the logits that the decode call gave
for each served token, for the comparison with the reference. ``Probe`` wraps
the calls into each layer from the benchmark's side, in the traced run, with
``jax.profiler.TraceAnnotation`` so that its spans share the profiler's clock:
``ServingEngine.step``, ``paged_decode_step`` as the engine calls it, and
``PagedKVPool.demote`` / ``promote``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import pathlib
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import weights as wts

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def import_program():
    """Put the checkout's ``src`` on the path; fail where the program is absent."""
    if not (SRC / "repro").is_dir():
        raise FileNotFoundError(f"the program is not in this checkout ({SRC} is missing)")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def arch_config(cfg: dict, family):
    """The program's ArchConfig, as the configuration's architecture module
    `family` builds it from the program's named architectures."""
    from repro.configs import get_config

    return family.arch_config(cfg, get_config)


def program_params(cfg: dict, seed: int, family):
    """The weights in the program's layout and served type, made on the device
    in one jitted call from the seed by the architecture module `family`."""
    return jax.jit(functools.partial(family.program_params, cfg))(wts.seed_key(seed))


def build_engine(arch, params, *, hot_pool_bytes: int, page_size: int,
                 max_batch: int, max_pages_per_seq: int):
    """The program's engine, with as many hot slots as `hot_pool_bytes` holds at
    the page size the program's own pool reports."""
    from repro.core import emucxl as ecxl
    from repro.core.policy import make_policy
    from repro.serving.engine import ServingEngine

    lib = ecxl.default_instance()
    if not lib._initialized:
        lib.init()

    def engine(num_slots):
        return ServingEngine(params, arch, num_slots=num_slots, page_size=page_size,
                             max_batch=max_batch, max_pages_per_seq=max_pages_per_seq,
                             policy=make_policy("policy1"))

    page_bytes = engine(1).pool._page_bytes()
    return engine(hot_pool_bytes // page_bytes), page_bytes


class ServedLogits:
    """The logits of every served token, as the program's decode call gave them.

    Wraps the engine's ``_decode_batch``, to learn which request each row of a
    decode call is, and ``paged_decode_step``, whose first output, the logits,
    it starts copying to the host at once; the step's other arguments and
    outputs are passed through unread. ``collect``, called after each engine
    step, keeps the rows that produced a token: those of requests past their
    last prompt token. ``batch_rows`` counts the rows of every decode call."""

    def __init__(self, eng):
        import repro.serving.engine as engine_mod

        self.eng, self._mod = eng, engine_mod
        self.rows: Dict[int, List[np.ndarray]] = {}
        self.batch_rows: List[int] = []
        self._pending: list = []
        self._batch: list = []
        decode, decode_batch = engine_mod.paged_decode_step, eng._decode_batch
        self._decode = decode

        def watched_batch(batch):
            self._batch = [(r.rid, r.position + 1 >= len(r.prompt)) for r in batch]
            self.batch_rows.append(len(batch))
            decode_batch(batch)

        def watched_decode(*args, **kwargs):
            out = decode(*args, **kwargs)
            out[0].copy_to_host_async()
            self._pending.append((out[0], self._batch))
            return out

        engine_mod.paged_decode_step = watched_decode
        eng._decode_batch = watched_batch

    def collect(self) -> None:
        for logits, batch in self._pending:
            rows = np.asarray(logits)
            for i, (rid, served) in enumerate(batch):
                if served:
                    self.rows.setdefault(rid, []).append(rows[i].copy())
        self._pending.clear()

    def close(self) -> None:
        self._mod.paged_decode_step = self._decode
        del self.eng._decode_batch


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    depth: int


class Probe:
    """Spans and counters of one traced window, taken around the program's calls.

    The decode call is wrapped whatever its arguments; each row's context (its
    position + 1) is read from the batch the engine's ``_decode_batch`` was
    given. After a tier move the probe waits for every device array the pool
    holds."""

    def __init__(self, eng):
        import repro.serving.engine as engine_mod

        self.eng, self._mod = eng, engine_mod
        self.spans: List[Span] = []
        self.decode_contexts: List[List[int]] = []   # per decode call: each position + 1
        self._depth = 0
        decode, step, pool = engine_mod.paged_decode_step, eng.step, eng.pool
        decode_batch, demote, promote = eng._decode_batch, pool.demote, pool.promote
        self._decode, self._decode_batch = decode, decode_batch

        def traced_batch(batch):
            self.decode_contexts.append([r.position + 1 for r in batch])
            decode_batch(batch)

        def traced_decode(*args, **kwargs):
            with self.span("paged_decode_step"):
                out = decode(*args, **kwargs)
                jax.block_until_ready(out[0])
            return out

        def traced_step():
            with self.span("ServingEngine.step"):
                step()

        def pool_arrays():
            return [v for v in vars(pool).values() if isinstance(v, jax.Array)]

        def traced_demote(seq_id, page_idx):
            with self.span("PagedKVPool.demote"):
                demote(seq_id, page_idx)
                jax.block_until_ready(pool_arrays())

        def traced_promote(seq_id, page_idx):
            with self.span("PagedKVPool.promote"):
                slot = promote(seq_id, page_idx)
                jax.block_until_ready(pool_arrays())
            return slot

        engine_mod.paged_decode_step = traced_decode
        eng.step, eng._decode_batch = traced_step, traced_batch
        pool.demote, pool.promote = traced_demote, traced_promote

    @contextlib.contextmanager
    def span(self, name: str):
        depth, t0 = self._depth, time.perf_counter()
        self._depth += 1
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self._depth -= 1
            self.spans.append(Span(name, t0, time.perf_counter(), depth))

    def close(self) -> None:
        self._mod.paged_decode_step = self._decode
        self.eng._decode_batch = self._decode_batch
        del self.eng.step, self.eng.pool.demote, self.eng.pool.promote


def device_memory_peak(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")
