"""Run one cell of the benchmark once, on the accelerator it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout; its configuration is ``bench/configs/<config>.json``, its
traffic ``bench/traffic/<traffic>.json``, its correctness limits
``bench/limits/<workload>.json``, and the configuration's ``mode``
(``bench/modes/<mode>.py``) drives the program. With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` a short
profiled window gives its per-layer metrics, each computed by
``bench/metrics/<metric>.py`` from the reduced trace
(``bench/trace_reduce.py``).

Earlier lines name the device, the compile cache and the compiles seen
inside the window. The last lines of standard error, and the ``checks``
key that closes the result, give each number compared for ``correct``
beside its limit. The last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
"checks"}``. With no TPU, fewer chips than the cell asks for, or a device
kind with no published peaks, the run exits 1 and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class CompileCounter:
    """Counts executables built or loaded from the persistent cache, and
    functions traced, through JAX's monitoring events."""

    EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name in self.EVENTS:
            self.count += 1

    def _duration(self, name, _secs, **_):
        self._event(name)

    def reset(self):
        self.count = 0


class RunContext:
    """What a mode needs from the harness."""

    def __init__(self, *, config, traffic, seed, seconds, trace, chips,
                 devices, trace_dir=None, process_start=PROCESS_START):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.chips, self.devices = chips, devices
        self.trace_dir = trace_dir
        self.process_start = process_start
        self.compiles = CompileCounter()
        self.notes = []

    def note(self, msg: str) -> None:
        self.notes.append(msg)
        print(msg, file=sys.stderr, flush=True)

    def since_start(self) -> float:
        return time.time() - self.process_start

    def setup_s(self, window_start_perf: float) -> float:
        """Seconds from process start to ``window_start_perf``."""
        return window_start_perf - time.perf_counter() + time.time() \
            - self.process_start

    @contextlib.contextmanager
    def window(self):
        """The measured window: a host span, profiled when tracing."""
        import jax
        if self.trace:
            self.trace_start()
            try:
                yield
            finally:
                self.trace_stop()
        else:
            with jax.profiler.TraceAnnotation("bench/window"):
                yield

    def trace_start(self):
        """Start the profiler and open the ``bench/window`` host span."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench/window")
        self._span.__enter__()

    def trace_stop(self):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        """Peak device memory of the fullest chip: buffers allocated
        plus the space reserved for the programs' temporaries, which the
        TPU runtime counts apart (``peak_bytes_reserved``)."""
        peaks = []
        for d in self.devices[:self.chips]:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0))
                         + int(stats.get("peak_bytes_reserved", 0)))
        return max(peaks) if peaks else 0


def load_cell(workload: str, root: Path = ROOT):
    """(spec, cell, config, traffic, limits) of a cell of BENCHMARK.json;
    a cell with no limits file yet has no limits (and is not correct)."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    config, traffic = load_files(cell["config"], cell["traffic"])
    lim = BENCH / "limits" / f"{workload}.json"
    limits = json.loads(lim.read_text()) if lim.exists() else {}
    return spec, cell, config, traffic, limits


def load_files(config: str, traffic: str):
    """A configuration's and a traffic mix's files, by name."""
    return (json.loads((BENCH / "configs" / f"{config}.json").read_text()),
            json.loads((BENCH / "traffic" / f"{traffic}.json").read_text()))


def applies(metric: dict, cell: dict, reported) -> bool:
    """Whether ``metric`` is this cell's: listed for it, or, without a
    list, reported by it (end-to-end) or moving a metric it reports."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def judge(checks: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}) of the compared numbers."""
    out, ok = {}, True
    for name, value in checks.items():
        lim = limits[name]
        out[name] = {"value": value, "limit": lim}
        ok = ok and math.isfinite(value) and value <= lim
    return ok and set(checks) == set(limits), out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace into this directory")
    args = ap.parse_args(argv)

    spec, cell, config, traffic, limits = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import peaks, trace_reduce

    devs = jax.devices()
    d0 = devs[0]
    print(f"[device] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    print(f"[setup] compile cache: {cache}", flush=True)
    if d0.platform != "tpu":
        print(f"bench: no TPU (first device is {d0.platform})",
              file=sys.stderr)
        return 1
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    try:
        peak = peaks.lookup(d0.device_kind)
    except peaks.UnknownDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1

    mode = importlib.import_module(f"bench.modes.{config['mode']}")
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    ctx = RunContext(config=config, traffic=traffic, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     chips=cell["chips"], devices=devs, trace_dir=tmp)
    try:
        out = mode.run(ctx)
        print(f"[window] compiles inside the window: "
              f"{out['window_compiles']}", flush=True)
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": cell["chips"],
                  "memory_peak_bytes": out["memory_peak_bytes"]}
        result = {}
        if args.trace:
            files = sorted(Path(tmp).rglob("*.xplane.pb"))
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                for f in files:
                    shutil.copy(f, args.keep_trace)
            red = trace_reduce.reduce(str(files[-1]))
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            lctx = dict(out["layer_ctx"], trace=red, peak=peak, notes=[])
            metrics = {}
            for m in spec["per_layer"]:
                if applies(m, cell, out["metrics"]):
                    v = metric_reader(m["name"])(lctx)
                    if v is not None:
                        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            for msg in lctx["notes"]:
                ctx.note(msg)
            result["breakdown"] = {"device_ops": red.top_ops,
                                   "idle_gaps": red.idle_gaps}
        else:
            metrics = {m["name"]: {"value": out["metrics"][m["name"]],
                                   "unit": m["unit"]}
                       for m in spec["end_to_end"]
                       if applies(m, cell, out["metrics"])}
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    correct, checks = judge(out["checks"], limits)
    line = {"correct": bool(correct and out["attempted"] > 0
                            and not out["failed"]),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device, **result,
            "checks": checks}
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
