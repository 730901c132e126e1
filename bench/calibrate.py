"""Readings that set a cell's numbers, made on the chip in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3 ...
        For each seed, a run as the benchmark makes it, then the float8 control
        put in the program's place on the same prompts and served tokens:
        prints the numbers compared for both.
    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 --rates 0.2 0.4
        The knee sweep of an open-loop mix: one run at each offered rate.

The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.run import load_benchmark, load_config, log  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()

    import jax

    from bench import run, system, traffic
    from bench.reference import ops

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices}")
        return 2
    system.import_program()
    cell = {w["name"]: w for w in load_benchmark()["workloads"]}[args.workload]
    cfg = load_config(cell["config"])
    family = run.architecture(cfg)
    mix = traffic.load_mix(cell["traffic"])
    seq_len = -(-(mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"])
                // cfg["serving"]["page_size"]) * cfg["serving"]["page_size"]
    runs = ([(args.seeds[0], dict(mix, rate_per_s=r)) for r in args.rates]
            or [(s, mix) for s in args.seeds])
    for seed, m in runs:
        res = run.run_cell(cfg, m, [], seed, args.seconds, False, devices[0],
                           ROOT / "chiprun_out" / "trace" / "calibrate")
        row = {"seed": seed, "rate_per_s": m.get("rate_per_s"), "correct": res["correct"],
               "program": {k: c["value"] for k, c in res["compared"].items()},
               "metrics": res["metrics"], "load": res["load"],
               "peak": res["device"]["memory_peak_bytes"],
               "served_checked": sum(len(t) for _, t in res["sample"])}
        if not args.rates and not args.no_control and res["sample"]:
            low = family.served_rows(cfg, seed, res["sample"], seq_len,
                                     cfg["check"]["requests"], quant=ops.fp8)
            row["control"] = run.compare(res["reference_rows"], low,
                                         [r.argmax(axis=-1) for r in low])
        print("CALIBRATE " + json.dumps(row), flush=True)
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
