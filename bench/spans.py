"""Join the device trace to the program's own spans (``repro.obs.span``).

A span puts its name into the ``op_name`` metadata of every HLO
instruction traced inside it, and keeps it through ``jax.grad`` and
remat: ``jit(f)/train/grad/transpose(jvp())/.../checkpoint/
rematted_computation/routing/assign/sort`` is the backward pass's copy of
an instruction of ``routing/assign``. The trace's ``XLA Ops`` events are
named by the same instructions, but carry no scope. The profile holds
the scope all the same: its ``/host:metadata`` plane keeps each compiled
module that ran, as an ``HloProto`` with its metadata (``trace_modules``).
From the module's text comes the instruction name -> ``op_name`` map
(the *scope map*, ``scope_map``); here each device operation of the
window is given the spans its instruction's ``op_name`` holds. Nothing
is compiled for it and nothing is added to the run's set-up.

A span is a ``<subsystem>/<phase>`` pair of the program's subsystems
(``SUBSYSTEMS``). The innermost span of an operation is the last one in
its ``op_name``; it is a *leaf* unless it only groups others
(``GROUPING``: ``train/grad`` holds the whole forward and backward pass).
Time whose innermost span is a grouping one, or that has no span at all,
is *unclaimed*.

Host spans are the trainer's ``train/*`` annotations on the host
timeline; device-idle time in the window is split by the one that
covers it.

The readers get the reduction (``ctx["trace"]``) but not the profile's
path, so ``load`` finds the profile itself: the newest ``.xplane.pb``
under the temporary directories ``bench/run.py`` traces into
(``TRACE_DIRS``), kept only if its ``bench/window`` span is the one the
reduction measured. ``attribution(ctx)`` computes all of it once per
traced run and adds its notes:

* device time by leaf span, with the unclaimed share and the share of
  operation time whose instruction the scope map holds;
* the time whose leaf span is ``kernels/routed_attention_fused`` beside
  ``Reduced.kernel_s("routed_attention_fused")``;
* device-idle time per step by trainer span.

Without such a profile, or with one that holds no compiled module, it is
``None``; with a program that writes no span (an older checkout) every
quantity is 0. Either way the readers return ``None``, never 0.
"""
from __future__ import annotations

import re
import tempfile
import time
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench.trace_reduce import WINDOW_SPAN, Op, covered, read_planes, union

SUBSYSTEMS = ("train", "model", "routing", "kernels")
GROUPING = ("train/grad",)
TRAINER = "train/"            # prefix of the trainer's host spans
UNCLAIMED = "(unclaimed)"
SPAN = re.compile(r"(?:^|[/(])((?:%s)/[A-Za-z_]\w*)" % "|".join(SUBSYSTEMS))
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
OP_NAME = re.compile(r'\bop_name="([^"]*)"')
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
# the operand list of an instruction: the first "opcode(...)" after the
# shape, whose operands hold no parentheses
OPERANDS = re.compile(r"\s[a-z][\w\-]*\(([^()]*)\)")
REF = re.compile(r"%([\w.\-]+)")
CALLED = re.compile(r"\b(calls|body|condition|to_apply)=%?([\w.\-]+)")
KERNEL = "routed_attention_fused"
TRACE_DIRS = "bench-trace-*"   # bench/run.py's tempfile.mkdtemp prefix
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
CANDIDATES = 3                 # profiles looked at, newest first


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` ("" without one) of every
    instruction of a compiled module's text (``trace_modules``,
    ``Compiled.as_text()``).

    Instructions that XLA's TPU passes build late (the index-sorted
    rewrite of a scatter, clones sunk into loop bodies, layout copies)
    can carry no metadata while the work they do was traced under a
    span. Such an instruction takes, in this order, the ``op_name`` of
    the computation it calls (its root, else the first instruction that
    has one), of its first operand that has one, or of the instruction
    that calls the computation it sits in."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    where: Dict[str, str] = {}           # instruction -> its computation
    members: Dict[str, List[str]] = {}   # computation -> instructions
    root: Dict[str, str] = {}
    caller: Dict[str, str] = {}          # computation -> calling instr
    comp = ""
    for line in hlo_text.splitlines():
        c = COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            continue
        m = INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        where[name] = comp
        members.setdefault(comp, []).append(name)
        if line.lstrip().startswith("ROOT"):
            root[comp] = name
        rhs = line[m.end():]
        args = OPERANDS.search(rhs)
        operands[name] = REF.findall(args.group(1)) if args else []
        for kind, target in CALLED.findall(rhs):
            caller.setdefault(target, name)
            if kind == "calls":
                calls[name] = target

    memo: Dict[str, str] = {}

    def of_comp(c: str, seen) -> str:
        first = [root[c]] if c in root else []
        for n in first + members.get(c, []):
            got = resolve(n, seen, up=False)
            if got:
                return got
        return ""

    def resolve(n: str, seen, up: bool = True) -> str:
        if own.get(n):
            return own[n]
        if n in memo or n in seen:
            return memo.get(n, "")
        seen = seen | {n}
        got = of_comp(calls[n], seen) if n in calls else ""
        for a in operands.get(n, []):
            if got:
                break
            if a in own:
                got = resolve(a, seen, up=False)
        if not got and up and where.get(n) in caller:
            got = resolve(caller[where[n]], seen)
        if up:
            memo[n] = got
        return got

    return {n: resolve(n, frozenset()) for n in own}


def _fields(buf: bytes, start: int = 0,
            end: Optional[int] = None) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of the protobuf message in
    ``buf[start:end]``; a length-delimited value is its (start, end)."""
    end = len(buf) if end is None else end
    i = start
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, wire, value


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _message(buf: bytes, span: Tuple[int, int]) -> Dict[int, list]:
    """The fields of the message at ``span``: number -> values."""
    out: Dict[int, list] = {}
    for num, _, value in _fields(buf, *span):
        out.setdefault(num, []).append(value)
    return out


def _str(buf: bytes, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode(errors="replace")


def trace_modules(path) -> List[str]:
    """The text of every compiled module a profile holds, largest first,
    with its ``op_name`` metadata. The profile (an ``XSpace``) keeps each
    module that ran as an ``HloProto``, the bytes of a stat named
    ``Hlo Proto`` on an event metadata of its ``/host:metadata`` plane."""
    from jax._src.lib import _jax

    buf = Path(path).read_bytes()
    out = []
    for plane in _message(buf, (0, len(buf))).get(1, []):   # planes
        fields = _message(buf, plane)
        if [_str(buf, n) for n in fields.get(2, [])] != [METADATA_PLANE]:
            continue
        # stat_metadata (5) and event_metadata (4) are maps, whose
        # entries hold the key in field 1 and the value in field 2
        hlo_stats = set()
        for entry in fields.get(5, []):
            for meta in _message(buf, entry).get(2, []):
                m = _message(buf, meta)                 # XStatMetadata
                if [_str(buf, n) for n in m.get(2, [])] == [HLO_STAT]:
                    hlo_stats.update(m.get(1, []))
        for entry in fields.get(4, []):
            for meta in _message(buf, entry).get(2, []):
                for stat in _message(buf, meta).get(5, []):
                    st = _message(buf, stat)            # XStat
                    if not hlo_stats & set(st.get(1, [])):
                        continue
                    for proto in st.get(6, []):         # bytes_value
                        for module in _message(buf, proto).get(1, []):
                            out.append(_jax.HloModule
                                       .from_serialized_hlo_module_proto(
                                           bytes(buf[module[0]:module[1]]))
                                       .to_string())
    return sorted(out, key=len, reverse=True)


def trace_scopes(path) -> Dict[str, str]:
    """The scope map of every module a profile holds; on a name that two
    modules share, the larger module's instruction wins."""
    out: Dict[str, str] = {}
    for text in trace_modules(path):
        for name, op in scope_map(text).items():
            out.setdefault(name, op)
    return out


def trace_files() -> List[Path]:
    """The profiles under the directories ``bench/run.py`` traces into,
    newest first."""
    return sorted(Path(tempfile.gettempdir()).glob(
        f"{TRACE_DIRS}/**/*.xplane.pb"),
        key=lambda p: p.stat().st_mtime, reverse=True)


def window_s(host: Sequence[Op]) -> Optional[float]:
    """The ``bench/window`` span's length, as ``reduce_ops`` takes it."""
    wins = [h for h in host if h.name == WINDOW_SPAN]
    return (wins[-1].end - wins[0].start) / 1e9 if wins else None


def load(red) -> Optional[tuple]:
    """(device ops by chip, host spans, scope map) of the profile that
    the reduction ``red`` was made from; ``None`` if none is found."""
    for path in trace_files()[:CANDIDATES]:
        try:
            devices, host = read_planes(path)
            if window_s(host) != red.window_s:
                continue
            return devices, host, trace_scopes(path)
        except (OSError, RuntimeError, ValueError, IndexError):
            continue
    return None


def spans_of(op_name: str) -> List[str]:
    """The program spans an ``op_name`` holds, outermost first."""
    return SPAN.findall(op_name)


def leaf(op_name: str) -> str:
    """The innermost span, or ``UNCLAIMED`` if it is a grouping span or
    there is none."""
    held = spans_of(op_name)
    if not held or held[-1] in GROUPING:
        return UNCLAIMED
    return held[-1]


def device_by_leaf(op_s: Dict[str, float],
                   scopes: Dict[str, str]) -> Dict[str, float]:
    """Seconds of operations by leaf span; operations whose instruction
    the map lacks are left out."""
    out: Dict[str, float] = {}
    for name, s in op_s.items():
        if name in scopes:
            k = leaf(scopes[name])
            out[k] = out.get(k, 0.0) + s
    return out


def span_s(op_s: Dict[str, float], scopes: Dict[str, str],
           span: str) -> float:
    """Seconds of operations whose ``op_name`` holds ``span``."""
    return sum(s for name, s in op_s.items()
               if span in spans_of(scopes.get(name, "")))


def idle_by_host_span(devices: Dict[int, List[Op]], host: Sequence[Op],
                      prefix: str = TRAINER) -> Dict[str, float]:
    """Device-idle seconds inside the ``bench/window`` host span, mean
    over the chips, by the host span named ``prefix...`` that covers
    them (the spans of one thread do not overlap); ``""`` holds the idle
    time no such span covers."""
    wins = [h for h in host if h.name == WINDOW_SPAN]
    if not wins or not devices:
        return {}
    w0, w1 = wins[0].start, wins[-1].end
    named = [h for h in host if h.name.startswith(prefix)
             and h.end > w0 and h.start < w1]
    out: Dict[str, float] = {}
    for ops in devices.values():
        busy = union((max(o.start, w0), min(o.end, w1)) for o in ops
                     if o.end > w0 and o.start < w1)
        idle = (w1 - w0) - sum(b - a for a, b in busy)
        in_spans = 0
        for h in named:
            a, b = max(h.start, w0), min(h.end, w1)
            gap = (b - a) - covered(busy, a, b)
            out[h.name] = out.get(h.name, 0.0) + gap
            in_spans += gap
        out[""] = out.get("", 0.0) + idle - in_spans
    n = len(devices)
    return {k: v / n / 1e9 for k, v in out.items()}


def attribution(ctx: dict) -> Optional[dict]:
    """The run's span attribution, computed once per reader context;
    ``None`` outside a traced training run whose profile holds the
    compiled step."""
    if ctx.get("mode") != "train" or "trace" not in ctx:
        return None
    if "_spans" in ctx:
        return ctx["_spans"]
    ctx["_spans"] = None
    t0 = time.perf_counter()
    got = load(ctx["trace"])
    if got is None or not got[2]:
        ctx["notes"].append(
            "[spans] no profile of this window with a compiled module "
            f"under {tempfile.gettempdir()}/{TRACE_DIRS}: no span metric")
        return None
    devices, host, scopes = got
    ctx["notes"].append(
        f"[spans] profile and scope map of {len(scopes)} instructions "
        f"read in {time.perf_counter() - t0:.2f} s, after the window")
    red, steps = ctx["trace"], ctx["steps"]
    total = sum(red.op_s.values())
    mapped = sum(s for n, s in red.op_s.items() if n in scopes)
    by_leaf = device_by_leaf(red.op_s, scopes)
    idle = idle_by_host_span(devices, host)
    out = {"scopes": scopes, "by_leaf": by_leaf,
           "mapped_share": mapped / total if total else 0.0,
           "idle": idle}
    ctx["_spans"] = out
    notes = ctx["notes"]
    busy = red.busy_s
    rows = sorted(by_leaf.items(), key=lambda kv: -kv[1])
    notes.append(
        "[spans] device time by leaf span (of busy "
        f"{busy:.6f} s): " + ", ".join(
            f"{k} {v:.6f} s {100 * v / busy:.2f}%" for k, v in rows))
    top: Dict[str, List] = {}
    for name, sec in sorted(red.op_s.items(), key=lambda kv: -kv[1]):
        if name in scopes and len(top.setdefault(
                leaf(scopes[name]), [])) < 3:
            top[leaf(scopes[name])].append(f"{name} {sec:.6f} s")
    notes.append("[spans] longest operations by leaf span: " + "; ".join(
        f"{k}: {', '.join(top[k])}" for k, _ in rows))
    claimed = sum(v for k, v in by_leaf.items() if k != UNCLAIMED)
    notes.append(
        f"[spans] leaf spans claim {100 * claimed / busy:.2f}% of busy "
        f"time; the scope map holds the instructions of "
        f"{100 * out['mapped_share']:.2f}% of operation time")
    notes.append(
        f"[spans] leaf span kernels/{KERNEL} "
        f"{by_leaf.get('kernels/' + KERNEL, 0.0):.6f} s; "
        f"Reduced.kernel_s({KERNEL!r}) {red.kernel_s(KERNEL):.6f} s")
    if idle and steps:
        all_idle = sum(idle.values())
        by_span = sorted(((k, v) for k, v in idle.items() if k),
                       key=lambda kv: -kv[1])
        notes.append(
            "[spans] device idle per step by trainer span: " + "".join(
                f"{k} {1e3 * v / steps:.4f} ms, " for k, v in by_span)
            + f"no trainer span {1e3 * idle.get('', 0.0) / steps:.4f} ms;"
            f" trainer spans cover "
            f"{100 * (all_idle - idle.get('', 0.0)) / max(all_idle, 1e-30):.2f}"
            f"% of idle time")
    return out
