"""Reduce a JAX profiler trace (``.xplane.pb``) to per-layer numbers.

A TPU trace holds one plane per chip (``/device:TPU:<i>``) whose
``XLA Ops`` line carries every operation the chip ran, with its start
and duration in nanoseconds, and host planes whose lines carry the host
threads' spans (``jax.profiler.TraceAnnotation``). The benchmark wraps
its traced window in the host span ``bench/window``; everything here is
measured inside that span.

* busy time: the union of a chip's operation intervals (so overlapping
  asynchronous operations count once), averaged over the chips;
* per-operation time: each event of the ``XLA Ops`` line is named by its
  HLO instruction (the event name is the instruction's text,
  ``%fusion.12 = f32[...] fusion(...)``, and the name is what precedes
  `` = ``). Control-flow instructions (``while``, ``conditional``,
  ``call``) span the operations of their bodies, which the line also
  holds, so they count towards busy time but not as operations;
* per-kernel time: the summed durations of operations named after the
  kernel (a Pallas kernel's instruction takes the name of the jitted
  wrapper that calls it, e.g. ``routed_attention_fused.3``), averaged
  over the chips;
* exposed collective time: the part of collective operations' intervals
  in which no other operation runs on that chip;
* the breakdown: the operations that took most time, and the longest
  idle gaps named by the host span that covers most of each.

``python -m bench.trace_reduce <trace.xplane.pb>`` prints the reduction.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench/window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|psum|allreduce)", re.I)
INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
CONTAINERS = ("while", "conditional", "call")
# host lines that are thread-pool bookkeeping, not work
HOST_NOISE = re.compile(r"^(ThreadpoolListener|SlinkyThreadPool)")


@dataclass
class Op:
    name: str
    start: int          # ns
    end: int            # ns
    container: bool = False
    text: str = ""      # the instruction's text, shapes and operands


def device_op(text: str, start: int, end: int) -> Op:
    """An ``XLA Ops`` event: its instruction name, and whether it is a
    control-flow instruction (XLA names them after their opcode:
    ``while.13``) that spans other events."""
    m = INSTRUCTION.match(text)
    name = m.group(1) if m else text
    return Op(name, start, end,
              container=name.split(".")[0] in CONTAINERS, text=text)


@dataclass
class Reduced:
    window_s: float
    chips: int
    busy_s: float                       # mean over chips
    op_s: Dict[str, float]              # name -> seconds, mean over chips
    op_text: Dict[str, str]             # name -> instruction text
    collective_s: float                 # mean over chips
    collective_exposed_s: float         # mean over chips
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def kernel_s(self, prefix: str) -> float:
        """Seconds of operations named ``prefix`` or ``prefix.<n>``,
        averaged over the chips."""
        pat = re.compile(rf"^{re.escape(prefix)}(\.\d+)?$")
        return sum(s for n, s in self.op_s.items() if pat.match(n))

    def matching_s(self, *fragments: str) -> float:
        """Seconds of operations whose instruction text holds every one
        of ``fragments`` (e.g. a Pallas call's target and an operand
        shape), averaged over the chips."""
        return sum(s for n, s in self.op_s.items()
                   if all(f in self.op_text[n] for f in fragments))

    def as_dict(self) -> dict:
        return {"window_s": self.window_s, "chips": self.chips,
                "busy_s": self.busy_s,
                "collective_s": self.collective_s,
                "collective_exposed_s": self.collective_exposed_s,
                "top_ops": self.top_ops, "idle_gaps": self.idle_gaps}


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: Sequence[Tuple[int, int]], a: int, b: int) -> int:
    """Length of [a, b) covered by disjoint sorted intervals."""
    tot = 0
    i = max(0, bisect.bisect_right(merged, (a, a)) - 1)
    while i < len(merged) and merged[i][0] < b:
        lo, hi = max(a, merged[i][0]), min(b, merged[i][1])
        if hi > lo:
            tot += hi - lo
        i += 1
    return tot


def read_planes(path: str):
    """(device ops by chip index, host spans) from a trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[int, List[Op]] = {}
    host: List[Op] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    device_op(e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                    for e in line.events)
            elif not m and plane.name.startswith("/host:"):
                host.extend(
                    Op(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if not HOST_NOISE.match(e.name))
    return devices, host


def reduce(path: str, top: int = 10) -> Reduced:
    devices, host = read_planes(path)
    if not devices:
        raise ValueError(f"{path}: no {OPS_LINE!r} line on any TPU plane")
    return reduce_ops(devices, host, top)


def reduce_ops(devices: Dict[int, List[Op]], host: List[Op],
               top: int = 10) -> Reduced:
    """The reduction of device operations by chip and host spans."""
    wins = [h for h in host if h.name == WINDOW_SPAN]
    if wins:
        w0, w1 = wins[0].start, wins[-1].end
    else:
        w0 = min(o.start for ops in devices.values() for o in ops)
        w1 = max(o.end for ops in devices.values() for o in ops)
    n = len(devices)
    busy = coll = coll_exposed = 0
    op_ns: Dict[str, float] = {}
    op_text: Dict[str, str] = {}
    gaps: List[Tuple[int, int]] = []
    for ops in devices.values():
        ops = [Op(o.name, max(o.start, w0), min(o.end, w1), o.container,
                  o.text) for o in ops if o.end > w0 and o.start < w1]
        merged = union((o.start, o.end) for o in ops)
        busy += sum(b - a for a, b in merged)
        ops = [o for o in ops if not o.container]
        for o in ops:
            op_ns[o.name] = op_ns.get(o.name, 0) + (o.end - o.start) / n
            op_text.setdefault(o.name, o.text)
        colls = [o for o in ops if COLLECTIVE.match(o.name)]
        if colls:
            cm = union((o.start, o.end) for o in colls)
            other = union((o.start, o.end) for o in ops
                          if not COLLECTIVE.match(o.name))
            coll += sum(b - a for a, b in cm)
            coll_exposed += sum((b - a) - covered(other, a, b)
                                for a, b in cm)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    top_ops = sorted(((k, v / 1e9) for k, v in op_ns.items()),
                     key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [(_host_cause(host, a, b), (b - a) / 1e9) for a, b in gaps[:top]]
    return Reduced(window_s=(w1 - w0) / 1e9, chips=n, busy_s=busy / n / 1e9,
                   op_s={k: v / 1e9 for k, v in op_ns.items()},
                   op_text=op_text,
                   collective_s=coll / n / 1e9,
                   collective_exposed_s=coll_exposed / n / 1e9,
                   top_ops=top_ops, idle_gaps=idle)


def _host_cause(host: Sequence[Op], a: int, b: int) -> str:
    """The host span that covers most of [a, b); the shortest on ties."""
    best: Optional[Tuple[int, int, str]] = None
    for h in host:
        if h.name == WINDOW_SPAN:
            continue
        ov = min(b, h.end) - max(a, h.start)
        if ov <= 0:
            continue
        key = (ov, -(h.end - h.start), h.name)
        if best is None or key > best:
            best = key
    return best[2] if best else "no host span"


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]).as_dict(), indent=1))
