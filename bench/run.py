"""One run of one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<name>.json``); the configuration names its
architecture module (``"reference"``), through which alone the run knows the
model's family (``ARCH_INTERFACE``). The run makes the weights from
the seed, builds the program's serving engine, warms up every batch size the
engine can run, starts the mix's schedule, and measures the window of
``--seconds`` once the preroll has loaded the engine; it keeps serving after the
window closes until every request due in the window has its first token. It
then frees the engine and compares a sample of the served tokens, and the
logits that produced them, with the float32 reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the profiler records the window, spans wrap each layer's calls,
and the result carries the per-layer metrics (``bench/metrics/<name>.py``).
Details go to earlier lines; the last line of standard output is one JSON
object. Without a TPU, or with fewer chips than the cell asks for, or where the
program or the chip's peaks are missing, the run exits with code 2 and prints no
result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

WINDOW_SPAN = "bench.window"
SPAN_NAMES = ("ServingEngine.step", "paged_decode_step", "PagedKVPool.demote",
              "PagedKVPool.promote")


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# --------------------------------------------------------------------- the cell
def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


# What an architecture module (a configuration's "reference") provides, cfg
# being the configuration file as run:
#   global_leaves(cfg), layer_groups(cfg): the weight leaves, as bench/weights.py
#       reads them: a table of global leaves, and {group: (table, layers)};
#   arch_config(cfg, get_config): the program's ArchConfig, `get_config` being
#       the program's lookup of its named architectures;
#   program_params(cfg, key): the program's weights, made under jit from the
#       seed's key;
#   served_rows(cfg, seed, pairs, seq_len, batch, quant): the float32
#       reference's logit rows at the served positions of each (prompt, served
#       tokens) pair, in blocks of `batch` padded to `seq_len`, `quant` rounding
#       the inputs of every matrix product (bench/reference/ops.py);
#   decode_step_flops(cfg, contexts): model FLOPs of one decode step;
#   paged_attention_cost(cfg, contexts, page_size): (FLOPs, bytes) of the paged
#       attention kernel in one decode step.
ARCH_INTERFACE = ("global_leaves", "layer_groups", "arch_config", "program_params",
                  "served_rows", "decode_step_flops", "paged_attention_cost")


def architecture(cfg: dict):
    """The configuration's architecture module: the file its ``"reference"``
    names, relative to the checkout's root, imported by its dotted name."""
    rel = pathlib.PurePosixPath(cfg["reference"])
    module = importlib.import_module(".".join(rel.with_suffix("").parts))
    missing = [n for n in ARCH_INTERFACE if not callable(getattr(module, n, None))]
    if missing:
        raise AttributeError(f"{cfg['reference']} lacks {', '.join(missing)}")
    return module


def metric_reader(name: str):
    """The ``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(entries: List[dict], workload: str) -> List[dict]:
    return [m for m in entries if workload in m.get("workloads", [workload])]


# --------------------------------------------------------------------- the load
@dataclasses.dataclass
class Record:
    """What one request saw, on the host clock."""
    due: float
    submitted: float
    prompt: List[int]
    max_new_tokens: int
    rid: int = -1
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: Optional[float] = None


class Driver:
    """Offers the schedule to the engine and records every token's time.

    The engine is synchronous, so arrivals are submitted between steps; an
    arrival's time counts from when it was due, so a long step delays it and
    the delay shows in its time to first token. After each step the served
    logits are collected."""

    def __init__(self, eng, arrivals, origin: float, logits):
        self.eng, self.origin, self.logits = eng, origin, logits
        self.pending = list(arrivals)
        self.records: List[Record] = []
        self.active: Dict[int, Record] = {}
        self.steps = 0

    def run_until(self, t_end: float, done=lambda: False) -> None:
        """Serve until `t_end`, or until `done()` holds once every arrival due
        has been submitted."""
        while True:
            now = time.perf_counter()
            while self.pending and self.origin + self.pending[0].due_s <= now:
                a = self.pending.pop(0)
                rec = Record(self.origin + a.due_s, now, a.prompt, a.max_new_tokens)
                rec.rid = self.eng.submit(a.prompt, max_new_tokens=a.max_new_tokens)
                self.records.append(rec)
                self.active[rec.rid] = rec
            if now >= t_end or done():
                return
            if not self.active:
                nxt = (self.origin + self.pending[0].due_s) if self.pending else t_end
                time.sleep(max(0.0, min(nxt, t_end) - now))
                continue
            self.eng.step()
            self.steps += 1
            self.logits.collect()
            self._collect(time.perf_counter())

    def _collect(self, t: float) -> None:
        for rid, rec in list(self.active.items()):
            r = self.eng.requests[rid]
            while len(rec.tokens) < len(r.generated):
                rec.tokens.append(r.generated[len(rec.tokens)])
                rec.token_times.append(t)
            if r.state == "finished":
                rec.done = t
                del self.active[rid]


def percentile(values: List[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    import numpy as np

    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(records: List[Record], w0: float, w1: float, t_end: float,
               setup_s: float) -> dict:
    """The end-to-end metrics, and the statistics logged beside them. A request
    due in the window counts its time to first token, taken after the window
    if need be; one with none when serving stops counts the time it waited."""
    due = [r for r in records if w0 <= r.due < w1]
    ttft = [(r.token_times[0] if r.token_times else t_end) - r.due for r in due]
    gaps = [b - a for r in records for a, b in zip(r.token_times, r.token_times[1:])
            if w0 <= a and b <= w1]
    out = {"setup_s": setup_s}
    if ttft:
        out.update(ttft_p50_s=percentile(ttft, 50), ttft_p75_s=percentile(ttft, 75),
                   ttft_p90_s=percentile(ttft, 90))
    if gaps:
        out.update(itl_p50_ms=1e3 * percentile(gaps, 50), itl_p90_ms=1e3 * percentile(gaps, 90),
                   itl_p99_ms=1e3 * percentile(gaps, 99))
    return out


# --------------------------------------------------------------------- the run
def warm_up(eng, max_batch: int) -> None:
    """Run every batch size once, and one page through the tier and back, so
    that nothing the window runs compiles there. Leaves the engine as found."""
    for b in range(1, max_batch + 1):
        rids = [eng.submit([1], max_new_tokens=1) for _ in range(b)]
        eng.step()
        if any(eng.requests[r].state != "finished" for r in rids):
            raise RuntimeError(f"warm-up batch of {b} did not finish in one step")
        for r in rids:
            del eng.requests[r]
    eng.pool.alloc_page(-1, 0)
    eng.pool.demote(-1, 0)
    eng.pool.promote(-1, 0)
    eng.pool.free_sequence(-1)


def check_sample(records: List[Record], n: int, seed: int) -> List[Record]:
    """The requests to compare: the longest finished, and the rest drawn from
    the seed among those finished. Where fewer than `n` finished, the
    unfinished ones that were served the most tokens fill the sample: their
    served tokens are as final as a finished request's."""
    import numpy as np

    done = [r for r in records if r.done is not None]
    done.sort(key=lambda r: (-(len(r.prompt) + len(r.tokens)), r.rid))
    rest = done[1:]
    pick = np.random.default_rng(seed).permutation(len(rest))[: max(0, n - 1)]
    sample = done[:1] + [rest[i] for i in sorted(pick)]
    open_ = sorted((r for r in records if r.done is None and r.tokens),
                   key=lambda r: (-len(r.tokens), r.rid))
    return sample + open_[: n - len(sample)]


def compare(reference, program, served) -> Dict[str, float]:
    """The numbers that decide ``correct``, over the sampled requests: for
    each, the float32 reference's logit rows at its served positions, the
    program's rows there, and the served tokens. ``max_logit_gap``: the widest
    gap by which a served token's reference logit lies below the reference's
    best. ``max_logprob_gap``: the widest gap between the program's and the
    reference's log-softmax, over every position and token."""
    from bench.reference import ops

    token = logprob = 0.0
    for ref, prog, toks in zip(reference, program, served):
        if len(prog) != len(toks) or not len(toks):
            return {"max_logit_gap": float("inf"), "max_logprob_gap": float("inf")}
        token = max(token, float(ops.token_gaps(ref, toks).max()))
        logprob = max(logprob, ops.logprob_gap(ref, prog[:, : ref.shape[-1]]))
    return {"max_logit_gap": token, "max_logprob_gap": logprob}


def run_cell(cfg: dict, mix: dict, per_layer: List[dict], seed: int, seconds: float,
             trace: bool, device, trace_dir: pathlib.Path,
             process_start: float = PROCESS_START) -> dict:
    """One run: set-up, the window, the reference. Returns the result's fields,
    with ``compared`` (each number compared and its limit), ``sample`` (the
    requests compared, with the program's logit rows) and ``load`` (counts of
    the window) besides."""
    import jax
    import numpy as np
    from jax import monitoring

    from bench import system, trace_reduce, traffic, weights
    from bench.peaks import peaks_for

    system.import_program()
    from repro.launch import compile_cache

    compile_cache.CACHE_DIR.mkdir(exist_ok=True)
    compile_cache.use_compile_cache()
    # Every program goes to the cache, however fast it compiled, so that no run
    # after a checkout's first compiles anything.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counts = {"compiled": 0, "loaded": 0}
    monitoring.register_event_listener(
        lambda ev, **kw: ev == "/jax/compilation_cache/cache_misses"
        and counts.__setitem__("compiled", counts["compiled"] + 1))
    monitoring.register_event_listener(
        lambda ev, **kw: ev == "/jax/compilation_cache/cache_hits"
        and counts.__setitem__("loaded", counts["loaded"] + 1))

    serve = cfg["serving"]
    family = architecture(cfg)
    arch = system.arch_config(cfg, family)
    params = system.program_params(cfg, seed, family)
    jax.block_until_ready(params)
    groups = family.layer_groups(cfg)
    log(f"weights: {weights.served_bytes(family.global_leaves(cfg), groups)} bytes served, "
        + ", ".join(f"{g}: {n} layers" for g, (_, n) in groups.items()))
    max_pages = -(-(mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"])
                  // serve["page_size"])
    eng, page_bytes = system.build_engine(
        arch, params, hot_pool_bytes=serve["hot_pool_bytes"],
        page_size=serve["page_size"], max_batch=serve["max_batch"],
        max_pages_per_seq=max_pages)
    del params
    log(f"engine: {eng.pool.num_slots} hot slots of {page_bytes} bytes "
        f"({serve['hot_pool_bytes']} bytes of hot pool), max_batch {serve['max_batch']}, "
        f"{max_pages} pages per sequence")
    warm_up(eng, serve["max_batch"])

    arrivals = traffic.schedule(mix, seed, cfg["vocab_size"], seconds)
    logits = system.ServedLogits(eng)
    origin = time.perf_counter() + mix["preroll_s"]       # the window opens here
    driver = Driver(eng, arrivals, origin, logits)
    driver.run_until(origin)

    probe = system.Probe(eng) if trace else None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        # Device activity and the benchmark's own annotations only: the Python
        # tracer would time every call of the host loop and slow it severalfold.
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level, options.python_tracer_level = 1, 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    w0 = time.perf_counter()
    setup_s = w0 - process_start
    before = dict(counts)
    steps0, calls0 = driver.steps, len(logits.batch_rows)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        driver.run_until(w0 + seconds)
    w1 = time.perf_counter()
    in_window = {k: counts[k] - before[k] for k in counts}
    steps = driver.steps - steps0
    occupancy = logits.batch_rows[calls0:]     # rows of each decode call in the window
    batch_mean = statistics.fmean(occupancy) if occupancy else 0.0
    batch_max = max(occupancy, default=0)
    if trace:
        jax.profiler.stop_trace()
        probe.close()
    records = driver.records
    due = [r for r in records if w0 <= r.due < w1]
    driver.run_until(w1 + mix["drain_s"], done=lambda: all(r.token_times for r in due))
    t_end = time.perf_counter()
    peak = system.device_memory_peak(device)

    late = [r.submitted - r.due for r in due]
    log(f"device: {device.platform} {device.device_kind!r}, id {device.id}")
    log(f"window: {w1 - w0:.3f} s, {steps} engine steps, {len(due)} requests due, "
        f"{eng.preemptions} preemptions in the whole run; served {t_end - w1:.3f} s "
        f"more until each had its first token")
    if late:
        log(f"generator lateness (submitted after due): median {statistics.median(late)} s, "
            f"max {max(late)} s over {len(late)} requests")
    log(f"decode calls in the window: {len(occupancy)}, rows per call: mean {batch_mean}, "
        f"max {batch_max}")
    log(f"compilations inside the window: {in_window['compiled']} "
        f"(programs loaded from the compilation cache there: {in_window['loaded']})")
    log(f"peak_bytes_in_use: {peak}")

    result = {"metrics": {}, "device": {
        "platform": device.platform, "kind": device.device_kind, "count": 1,
        "memory_peak_bytes": peak}}
    if trace:
        spans = [s for s in probe.spans if w0 <= s.start and s.end <= w1]
        tr = trace_reduce.load(str(trace_dir), SPAN_NAMES, WINDOW_SPAN)
        dev = sorted(tr.ops)[0] if tr.ops else None
        busy = trace_reduce.busy_ns(tr.ops[dev], tr.window) if dev else 0
        window_ns = tr.window[1] - tr.window[0]
        result["device"].update(busy_s=busy / 1e9, window_s=window_ns / 1e9)
        ctx = dict(cfg=cfg, family=family, page_size=serve["page_size"],
                   peaks=peaks_for(device.device_kind),
                   spans=spans, decode_contexts=probe.decode_contexts, trace=tr, device=dev)
        for m in per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)     # the reduction is kept, not the trace
        if dev:
            by_name: Dict[str, int] = {}
            for name, t in trace_reduce.time_by_op(tr.ops[dev], tr.window).items():
                short = trace_reduce.short_name(name)
                by_name[short] = by_name.get(short, 0) + t
            ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
            idle = sorted(trace_reduce.idle_by_label(tr, dev).items(),
                          key=lambda kv: -kv[1])[:10]
            result["breakdown"] = {"device_ops": [[n, t / 1e9] for n, t in ops],
                                   "idle_gaps": [[n, t / 1e9] for n, t in idle]}
    else:
        result["metrics"] = end_to_end(records, w0, w1, t_end, setup_s)
        log("statistics: " + ", ".join(f"{k} {v}" for k, v in result["metrics"].items()))

    # In flight at some time in the window: due before it closed, not done before it opened.
    attempted = [r for r in records if r.due < w1 and (r.done is None or r.done >= w0)]
    failed = [r for r in attempted if len(r.tokens) > r.max_new_tokens
              or any(not 0 <= t < cfg["vocab_size"] for t in r.tokens)]
    check = cfg["check"]
    sample = check_sample(records, check["requests"], seed)
    program_rows = [np.stack(logits.rows.get(r.rid, [])) if r.tokens else np.zeros((0, 1))
                    for r in sample]
    load = {"due": len(due), "in_flight": len(attempted),
            "finished": sum(1 for r in records if r.done and w0 <= r.done <= w1),
            "waiting": sum(1 for r in due if not r.token_times), "steps": steps,
            "batch_mean": batch_mean, "batch_max": batch_max, "preemptions": eng.preemptions}
    # Free the program's state before the reference takes the chip.
    logits.close()
    del driver, eng, probe, logits
    gc.collect()
    t_ref = time.perf_counter()
    pairs = [(r.prompt, r.tokens) for r in sample]
    reference = family.served_rows(cfg, seed, pairs, seq_len=max_pages * serve["page_size"],
                                   batch=check["requests"])
    numbers = (compare(reference, program_rows, [r.tokens for r in sample]) if sample
               else {"max_logit_gap": float("inf"), "max_logprob_gap": float("inf")})
    served = sum(len(r.tokens) for r in sample)
    log(f"reference: {time.perf_counter() - t_ref} s; "
        f"whole run {time.perf_counter() - process_start} s")
    log(f"checked {served} served tokens of {len(sample)} requests "
        f"({sum(r.done is not None for r in sample)} finished) "
        f"(longest: {len(sample[0].prompt) if sample else 0} prompt + "
        f"{len(sample[0].tokens) if sample else 0} served tokens)")
    compared = {k: {"value": v, "limit": check[k]} for k, v in numbers.items()}
    result.update(
        correct=bool(all(c["value"] <= c["limit"] for c in compared.values())
                     and not failed and served > 0),
        attempted=len(attempted), failed=len(failed), compared=compared,
        sample=[(r.prompt, r.tokens) for r in sample], sample_rows=program_rows,
        reference_rows=reference, load=load)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    workload = cells[args.workload]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices}")
        return 2
    if len(devices) < workload["chips"]:
        log(f"{workload['chips']} chips needed, JAX found {len(devices)}")
        return 2
    from bench import peaks, system

    try:
        peaks.peaks_for(devices[0].device_kind)
        system.import_program()
    except (KeyError, FileNotFoundError) as e:
        log(str(e))
        return 2

    from bench import traffic

    cfg = load_config(workload["config"])
    mix = traffic.load_mix(workload["traffic"])
    per_layer = metrics_of(spec["per_layer"], args.workload) if args.trace else []
    result = run_cell(cfg, mix, per_layer, args.seed, args.seconds,
                      bool(args.trace), devices[0],
                      ROOT / "chiprun_out" / "trace" / args.workload)
    if not args.trace:
        wanted = {m["name"]: m["unit"] for m in metrics_of(spec["end_to_end"], args.workload)}
        result["metrics"] = {n: {"value": v, "unit": wanted[n]}
                             for n, v in result["metrics"].items() if n in wanted}
    compared = result.pop("compared")
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    line = {k: result[k] for k in ("correct", "attempted", "failed", "metrics", "device")}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["compared"] = compared
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
