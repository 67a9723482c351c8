"""repro.obs — the unified observability subsystem (DESIGN.md §10).

Three layers, all importable from here:

  metrics   Counter/Gauge/Histogram + Registry, JsonlSink (schema-
            versioned one-line-per-event records), StepSeries (trainer
            history adapter)
  routing   RoutingStats — the routing-health aux pytree computed inside
            the jitted step (occupancy entropy, dead clusters, centroid
            drift, balanced-vs-nearest mismatch, sampled attention
            recall) — plus summarize/flatten folds and the serving-side
            pages_health reader
  trace     span(name) — named_scope + TraceAnnotation around kernels,
            model layers, routing stages and train/engine phases;
            step_span(step) — one training step on the profiler's
            timeline; profile(log_dir) — on-demand xplane capture behind
            --profile-dir flags

This package sits at the bottom of the import DAG (jax + stdlib only):
core/, train/, serve/, kernels/ all report through it, so it must never
import them. Validate emitted JSONL with
``python -m repro.obs.schema file.jsonl``.
"""
from repro.obs import routing_stats  # noqa: F401
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               JsonlSink, Registry, SCHEMA_VERSION,
                               StepSeries, default_registry)
from repro.obs.routing_stats import (RoutingStats,  # noqa: F401
                                     compute_routing_stats, pages_health)
from repro.obs.schema import (SchemaError, validate_jsonl,  # noqa: F401
                              validate_record)
from repro.obs.trace import profile, span, step_span  # noqa: F401
