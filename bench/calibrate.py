"""Readings for the correctness limits of a cell, on many seeds in one
process: the program as the cell runs it, the control (the program's
own lower-precision path), or the program with a planted fault.

    python3 bench/calibrate.py --workload <name> --seeds 1 2 3 \\
        [--control] [--fault <name>] [--seconds 2]
    python3 bench/calibrate.py --config <config> --traffic <mix> ...

The second form reads a cell that ``BENCHMARK.json`` does not hold yet,
on one chip.

``--control`` applies the configuration's ``control`` entry: a ``dtype``
runs the program on its own path of that precision; a ``rounding``
reads the reference at that precision in the program's place. Faults
are ``faults.FAULTS[mode]``.

Prints one JSON line per seed with each compared number. Not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default=None)
    ap.add_argument("--config", default=None)
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--rates", type=float, nargs="*", default=None,
                    help="serving: offered rates (requests/s) to sweep, "
                         "each on every seed, in place of the traffic's")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.faults import FAULTS
    from bench.run import RunContext, load_cell, load_files

    if args.workload:
        _, cell, config, traffic, _ = load_cell(args.workload)
    else:
        cell = {"chips": 1}
        config, traffic = load_files(args.config, args.traffic)
    if args.control:
        ctl = config["control"]
        config["model"]["dtype"] = ctl.get("dtype", config["model"]["dtype"])
        config["control_rounding"] = ctl.get("rounding")
    mode = importlib.import_module(f"bench.modes.{config['mode']}")
    fault = FAULTS[config["mode"]][args.fault] if args.fault else None
    devs = jax.devices()
    print(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    runs = [(seed, rate) for rate in (args.rates or [None])
            for seed in args.seeds]
    for seed, rate in runs:
        t = time.time()
        if rate is not None:
            traffic = dict(traffic, rate_per_s=rate)
        ctx = RunContext(config=config, traffic=traffic, seed=seed,
                         seconds=args.seconds, trace=False,
                         chips=cell["chips"], devices=devs)
        out = mode.run(ctx, fault=fault)
        print(json.dumps({"seed": seed, "rate": rate,
                          "control": args.control,
                          "fault": args.fault,
                          "checks": {k: float(v) for k, v in
                                     out["checks"].items()},
                          "memory_peak_bytes": out["memory_peak_bytes"],
                          "metrics": out["metrics"],
                          "diag": out.get("diag"),
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "wall_s": time.time() - t}), flush=True)
        print(f"[memory] {devs[0].memory_stats()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
