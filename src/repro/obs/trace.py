"""Trace spans + on-demand profiler capture.

``span(name)`` names a region both ways a JAX program is observed:

  * ``jax.named_scope`` — inside a jit trace it tags the emitted HLO ops,
    so the region shows up named in xplane traces and compiled-module
    dumps (zero runtime cost; pure metadata);
  * ``jax.profiler.TraceAnnotation`` — on the host timeline it brackets
    the python-side region (engine admit/prefill/decode phases, dispatch
    of a train step), visible in the same xplane capture.

Span naming convention (DESIGN.md §10): ``<subsystem>/<phase>``.
Inside the compiled step: ``model/embed``, ``model/attention_proj``,
``model/ffn``, ``model/loss``, ``model/stack``, ``routing/assign``,
``routing/gather``, ``routing/attend``, ``routing/scatter``,
``routing/kmeans_update``, ``kernels/<kernel>``, ``train/grad``,
``train/exchange``, ``train/optimizer``. On the host: the trainer's
``train/data``, ``train/dispatch``, ``train/fetch``, ``train/checkpoint``
inside a per-step ``step_span``, and the engine's ``engine/admit``,
``engine/prefill``, ``engine/decode``. A device operation's innermost
span is the last one in its compiled instruction's ``op_name``; the
benchmark joins the device trace to it that way (``bench/spans.py``).

``step_span(step)`` marks one training step on the profiler's timeline
(``jax.profiler.StepTraceAnnotation``), so trace viewers group the
host spans and device operations by step.

``profile(log_dir)`` wraps ``jax.profiler.trace``: a context manager that
writes an xplane trace (viewable in TensorBoard / xprof) covering its
body, or a no-op when ``log_dir`` is falsy — so call sites can thread a
``--profile-dir`` flag straight through.
"""
from __future__ import annotations

import contextlib
import os

import jax


@contextlib.contextmanager
def span(name: str):
    """Name a region in both the HLO metadata and the host timeline."""
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


def step_span(step: int):
    """Mark training step ``step`` on the host timeline."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


@contextlib.contextmanager
def profile(log_dir, enabled: bool = True):
    """Capture an xplane profiler trace of the body into ``log_dir``
    (no-op when ``log_dir`` is falsy or ``enabled`` is False)."""
    if not log_dir or not enabled:
        yield
        return
    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(str(log_dir)):
        yield
