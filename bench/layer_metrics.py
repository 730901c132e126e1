"""The arithmetic behind the per-layer metrics of ``bench/metrics/``.

Each reader there takes the traced run's context: the benchmark's own spans
(``bench.system.Probe``) inside the window, its counters, the decode calls'
context lengths, the reduced device trace (``bench.trace_reduce``), and the
configuration's architecture module (``family``), which counts a step's
operations and bytes.
"""

from __future__ import annotations

import statistics
import sys
from typing import Optional

from bench import trace_reduce

STEP, DECODE = "ServingEngine.step", "paged_decode_step"
TIER = ("PagedKVPool.demote", "PagedKVPool.promote")


def sched_ms_per_step(ctx) -> Optional[float]:
    """Host ms per engine step outside the decode call and the tier moves."""
    steps = [s for s in ctx["spans"] if s.name == STEP and s.depth == 0]
    if not steps:
        return None
    inner = sum(s.end - s.start for s in ctx["spans"]
                if s.depth == 1 and s.name in (DECODE,) + TIER)
    return 1e3 * (sum(s.end - s.start for s in steps) - inner) / len(steps)


def decode_ms(ctx) -> Optional[float]:
    """Median host ms of a decode call until its logits are ready."""
    d = [s.end - s.start for s in ctx["spans"] if s.name == DECODE]
    return 1e3 * statistics.median(d) if d else None


def decode_mfu(ctx) -> Optional[float]:
    """Model FLOPs of the positions decoded over the time in decode calls at
    the chip's bf16 peak, in percent."""
    t = sum(s.end - s.start for s in ctx["spans"] if s.name == DECODE)
    if not t or not ctx["decode_contexts"]:
        return None
    work = sum(ctx["family"].decode_step_flops(ctx["cfg"], c) for c in ctx["decode_contexts"])
    return 100.0 * work / (t * ctx["peaks"]["bf16_flop_per_s"])


def kernel_roofline(ctx, op_names) -> Optional[float]:
    """Least time of the kernel's calls (live pages only) over their device time
    in the trace, in percent. Says on standard error which bound holds."""
    if not ctx["decode_contexts"]:
        return None
    f = b = 0
    for c in ctx["decode_contexts"]:
        cf, cb = ctx["family"].paged_attention_cost(ctx["cfg"], c, ctx["page_size"])
        f, b = f + cf, b + cb
    if ctx["trace"] is None or ctx["device"] is None:
        return None
    by_op = trace_reduce.time_by_op(ctx["trace"].ops[ctx["device"]], ctx["trace"].window)
    kernel_ns = sum(t for name, t in by_op.items() if any(k in name for k in op_names))
    if not kernel_ns:
        return None
    t_flops = f / ctx["peaks"]["bf16_flop_per_s"]
    t_bytes = b / ctx["peaks"]["hbm_byte_per_s"]
    print(f"paged attention: {f} FLOPs, {b} bytes of live pages, device time "
          f"{kernel_ns / 1e9} s; bound by {'bytes' if t_bytes >= t_flops else 'FLOPs'}",
          file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / (kernel_ns / 1e9)


def device_idle(ctx) -> Optional[float]:
    """Share of the traced window with no operation on the device, in percent."""
    if ctx["trace"] is None or ctx["device"] is None:
        return None
    tr = ctx["trace"]
    busy = trace_reduce.busy_ns(tr.ops[ctx["device"]], tr.window)
    return 100.0 * (1.0 - busy / (tr.window[1] - tr.window[0]))
