"""Benchmark entry point: one harness per paper table + roofline summary.

Prints ``name,us_per_call,derived`` CSV (one row per measured entity).
``us_per_call`` is the reduced-config CPU step wall-time; ``derived``
carries the table's quantity (paper reference value, measured ratio, JSD,
bits/dim, ...). TPU-projected numbers live in the roofline table
(EXPERIMENTS.md §Roofline), not here.

``--backend-sweep`` appends one row per registered attention backend
(repro.attn registry) with tok/s + peak-memory, so backend regressions
show up in the same report tables; ``--backend-sweep-only`` skips the
paper tables (fast per-push trend line).

``--routing-sweep`` appends the routing rows across N in {1k, 4k, 8k} x
{xla reference, pallas_fused kernel} (tok/s + memory_analysis peak +
device kind + interpret flag) and writes ``BENCH_routing.json`` at the
repo root (a CI artifact), including the analytic routing-vs-flash
roofline crossover; ``--routing-sweep-only`` runs just that (the
push-time CI bench job). ``--routing-check`` additionally gates the
sweep on the kernel's output parity with the xla reference.

``--obs-sweep`` appends routing-health telemetry rows (occupancy entropy
vs log k, dead clusters, balanced-vs-nearest mismatch, sampled attention
recall, stats-on tok/s) per sequence length; ``--obs-sweep-only`` runs
just those.
"""
import sys


FLAGS = ("--backend-sweep", "--backend-sweep-only",
         "--routing-sweep", "--routing-sweep-only", "--routing-check",
         "--obs-sweep", "--obs-sweep-only")


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    unknown = [a for a in argv if a not in FLAGS]
    if unknown:
        raise SystemExit(f"unknown arguments {unknown}; known: {FLAGS}")
    sweep = "--backend-sweep" in argv or "--backend-sweep-only" in argv
    routing_check = "--routing-check" in argv
    routing = ("--routing-sweep" in argv or "--routing-sweep-only" in argv
               or routing_check)
    obs = "--obs-sweep" in argv or "--obs-sweep-only" in argv
    # any -only flag skips the paper tables; the sweeps themselves compose
    tables = not any(a.endswith("-only") for a in argv)
    print("name,us_per_call,derived")
    if tables:
        from benchmarks.tables import ALL_TABLES
        for table in ALL_TABLES:
            for name, us, derived in table():
                print(f"{name},{us:.1f},{derived}")
                sys.stdout.flush()
    if sweep:
        from benchmarks.backend_sweep import backend_sweep_rows
        for name, us, derived in backend_sweep_rows():
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
    if routing:
        from benchmarks.routing_sweep import routing_sweep_rows, write_json
        rows, record = routing_sweep_rows(check=routing_check)
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
        write_json(record)
    if obs:
        from benchmarks.obs_sweep import obs_sweep_rows
        for name, us, derived in obs_sweep_rows():
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()


if __name__ == "__main__":
    main()
