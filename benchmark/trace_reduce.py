"""From a profiler trace to numbers: device busy/idle, device time by
operation name, and idle gaps named by what the host was doing.

The arithmetic works on plain event tuples ``(name, start_ns, dur_ns)`` so
it can be checked on a hand-made list; :func:`read_xplane` turns a JAX
``.xplane.pb`` into those tuples with nothing but ``jax.profiler``.

Every reader matches a device event by its CUT name (the instruction less
its number: ``fusion``, ``gmm``); XLA's fusions carry no name of the
program's, so ``breakdown`` alone books an event under the program's scope
(``kda_attn/linear_attn/delta_rule``, ``moe/route``) where :func:`summarize`
is handed the executable's ``{instruction name: op_name}`` map, which the
v5e's trace does not carry and the program's ``telemetry/device_scopes.py
instruction_scopes`` reads from the optimized HLO.

What the v5e's trace looks like (looked at by hand, PR 23): one plane per
chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per
executable run, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event
per HLO instruction; a ``while`` or ``conditional`` encloses its body's
events on the same line) and ``Steps``; host threads are lines of
``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans appear under
their own names.  Both planes are on one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # name, start_ns, dur_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"                       # the benchmark's own host spans
# segments of an ``op_name`` that say how JAX staged the instruction, not
# where the program was, and a segment that ends in an index
# (``deepspeed_tpu/telemetry/device_scopes.py``'s, copied: the yardstick
# keeps its own; ``tests/benchmark`` holds the two to one reading)
_WRAPPER = re.compile(
    r".*\(.*\)$|.*\..*|.*->.*|checkpoint$|rematted_computation$|remat\d*$"
    r"|while$"
    r"|body$|cond$|closed_call$|core_call$|pjit$|branch_\d+_fun$"
    r"|custom_[jv][vj]p_call(_jaxpr)?$")
_INDEXED = re.compile(r"^(.*_)\d+$")
_TOP_LEVEL = "(step)"
_DEPTH = 3                                   # names a scope keeps
_PALLAS = "pallas_call"                      # a kernel's op_name ends in it


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals as a sorted disjoint list."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to the window ``[lo, hi)``; those outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def self_times(events: Sequence[Event]) -> List[Event]:
    """Each event's duration minus the part its nested events cover (one
    line, where an enclosing op spans its body's ops).  Order: by start."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[n, s, d] for n, s, d in evs]
    stack: List[int] = []
    for i, (_, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= d
        stack.append(i)
    return [(n, s, max(d, 0.0)) for n, s, d in out]


def by_name(events: Iterable[Event]) -> Dict[str, float]:
    acc: Dict[str, float] = {}
    for name, _, d in events:
        acc[name] = acc.get(name, 0.0) + d
    return acc


class OpName(str):
    """A device event's cut name, which is all a reader sees, with the whole
    instruction name (``fusion.412``) beside it for :func:`xla_scope`."""
    instruction: str

    def __new__(cls, instruction: str):
        self = super().__new__(cls, re.sub(r"[.\-_]\d+$", "", instruction))
        self.instruction = instruction
        return self


def scope_name(op_name: str) -> str:
    """The program's scope of an ``op_name`` as ``device_scopes.scope_of``
    cuts it (the primitive and the transform wrappers dropped, an index
    folded, a name that comes again taking the path back to where it first
    stood), less the leading indexed scope, so that the layers of a stack add
    up, and then the first three names: ``jit(step_fn)/jvp(M)/layers_3/
    kda_attn/linear_attn/delta_rule/dot_general`` is
    ``kda_attn/linear_attn/delta_rule``."""
    path: List[str] = []
    for seg in op_name.split("/")[:-1]:
        if _WRAPPER.match(seg):
            continue
        seg = _INDEXED.sub(r"\1*", seg)
        if seg in path:
            del path[path.index(seg):]
        path.append(seg)
    if len(path) > 1 and path[0].endswith("_*"):
        del path[0]
    return "/".join(path[:_DEPTH]) or _TOP_LEVEL


def xla_scope(name: str, scopes: Dict[str, str]) -> Optional[str]:
    """The program's scope of an instruction (its whole name) that ``scopes``
    names and that
    is no Pallas custom call; ``None`` for a kernel and for an instruction
    without an ``op_name`` (the compiler's own copies)."""
    op_name = scopes.get(name)
    if op_name is None or op_name.rsplit("/", 1)[-1] == _PALLAS:
        return None
    return scope_name(op_name)


def booked_times(selfs: Sequence[Event], scopes: Dict[str, str]
                 ) -> Dict[str, float]:
    """Self time by what ``breakdown`` calls a device event: a Pallas custom
    call its kernel's name (its cut name, as ever); any other instruction
    the program's scope of its ``op_name`` (:func:`xla_scope`); one that has
    none its cut name.  A kernel is named after the scope it stands in
    (``attn``, ``self_attn``), so where XLA's instructions of a scope would
    fall under a kernel's name they are booked as ``<scope>/(xla)`` and the
    kernel keeps its name to itself."""
    cut: Dict[str, float] = {}
    scoped: Dict[str, float] = {}
    scope_of: Dict[str, Optional[str]] = {}     # an instruction runs a step
    for name, _, dur in selfs:
        instruction = getattr(name, "instruction", name)
        if instruction not in scope_of:
            scope_of[instruction] = xla_scope(instruction, scopes)
        scope = scope_of[instruction]
        into, key = (cut, str(name)) if scope is None else (scoped, scope)
        into[key] = into.get(key, 0.0) + dur
    for scope, dur in scoped.items():
        cut[scope + "/(xla)" if scope in cut else scope] = dur
    return cut


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi)`` left by merged ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def attribute(gap_list: Sequence[Tuple[float, float]],
              spans: Sequence[Event]) -> Tuple[Dict[str, float], List[Tuple[str, float]]]:
    """Name each idle gap by the host span that covers most of it (the
    innermost wins a tie by being shorter); ``unattributed`` when none
    overlaps.  Returns (seconds by span name, every gap as (name, s))."""
    spans = sorted(spans, key=lambda e: e[1])
    totals: Dict[str, float] = {}
    named: List[Tuple[str, float]] = []
    for a, b in gap_list:
        best, best_key = "unattributed", (0.0, 0.0)
        for name, s, d in spans:
            if s >= b:
                break
            ov = min(b, s + d) - max(a, s)
            if ov > 0 and (ov, -d) > best_key:
                best, best_key = name, (ov, -d)
        sec = (b - a) * 1e-9
        totals[best] = totals.get(best, 0.0) + sec
        named.append((best, sec))
    return totals, named


@dataclasses.dataclass
class TraceSummary:
    """Everything the per-layer readers take from a trace; seconds, each
    device quantity averaged over the chips that ran anything."""
    n_devices: int
    window_s: float
    busy_s: float
    op_self_s: Dict[str, float]              # device time by op name
    module_s: Dict[str, float]               # by executable name
    module_runs: Dict[str, int]
    gap_s_by_span: Dict[str, float]
    longest_gaps: List[Tuple[str, float]]
    # device time by booked_times; empty where summarize had no map
    booked_self_s: Dict[str, float] = dataclasses.field(default_factory=dict)

    def ops_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_self_s.items() if rx.search(k))

    def modules_matching(self, pattern: str) -> Tuple[float, int]:
        rx = re.compile(pattern)
        keys = [k for k in self.module_s if rx.search(k)]
        return (sum(self.module_s[k] for k in keys),
                sum(self.module_runs[k] for k in keys))

    def breakdown(self) -> dict:
        top = sorted((self.booked_self_s or self.op_self_s).items(),
                     key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.longest_gaps[:10]]}


def summarize(device_ops: Dict[int, Sequence[Event]],
              device_modules: Dict[int, Sequence[Event]],
              host_spans: Sequence[Event],
              window: Optional[Tuple[float, float]] = None,
              scopes: Optional[Dict[str, str]] = None) -> TraceSummary:
    """Reduce per-device op and module events plus the host's spans.  The
    window defaults to the extent of the outermost ``bench/window`` span,
    else to the extent of all device events.  ``scopes`` is the executable's
    ``{instruction name: op_name}``; it names ``breakdown``'s device
    operations (:func:`booked_times`) and moves nothing else."""
    if window is None:
        win = [e for e in host_spans if e[0] == SPAN_PREFIX + "window"]
        if win:
            window = (win[0][1], win[0][1] + win[0][2])
        else:
            all_ev = [e for evs in device_ops.values() for e in evs]
            if not all_ev:
                raise ValueError("trace holds no device operation")
            window = (min(e[1] for e in all_ev),
                      max(e[1] + e[2] for e in all_ev))
    lo, hi = window
    devs = [d for d, evs in device_ops.items() if clip(evs, lo, hi)]
    if not devs:
        raise ValueError("no operation ran on a device inside the window")
    n = len(devs)
    busy_s = 0.0
    op_self: Dict[str, float] = {}
    booked: Dict[str, float] = {}
    mod_s: Dict[str, float] = {}
    mod_runs: Dict[str, int] = {}
    for d in devs:
        evs = clip(device_ops[d], lo, hi)
        busy_s += sum(e - s for s, e in merge((s, s + dd) for _, s, dd in evs))
        selfs = self_times(evs)
        for k, v in by_name(selfs).items():
            op_self[k] = op_self.get(k, 0.0) + v
        if scopes:
            for k, v in booked_times(selfs, scopes).items():
                booked[k] = booked.get(k, 0.0) + v
        for nm, s, dd in clip(device_modules.get(d, ()), lo, hi):
            key = re.sub(r"\(.*\)$", "", nm)
            mod_s[key] = mod_s.get(key, 0.0) + dd
            mod_runs[key] = mod_runs.get(key, 0) + 1
    # gaps are named on the first device: one host drives them all
    first = clip(device_ops[devs[0]], lo, hi)
    gap_list = gaps(merge((s, s + dd) for _, s, dd in first), lo, hi)
    spans = [e for e in clip(host_spans, lo, hi)
             if e[0] != SPAN_PREFIX + "window"]
    totals, named = attribute(gap_list, spans)
    ns = 1e-9
    return TraceSummary(
        n_devices=n, window_s=(hi - lo) * ns, busy_s=busy_s * ns / n,
        op_self_s={k: v * ns / n for k, v in op_self.items()},
        module_s={k: v * ns / n for k, v in mod_s.items()},
        module_runs={k: int(round(v / n)) for k, v in mod_runs.items()},
        gap_s_by_span=totals,
        longest_gaps=sorted(named, key=lambda kv: -kv[1]),
        booked_self_s={k: v * ns / n for k, v in booked.items()})


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _op_name(event) -> OpName:
    """The HLO instruction name with its trailing number cut, so that the
    48 copies of one fusion add up; a Pallas kernel keeps its kernel name
    (the custom call's name is the kernel function's)."""
    return OpName(event.name.split(" = ")[0].lstrip("%"))


def read_xplane(path: str, device_plane=DEVICE_PLANE, ops_line: str = OPS_LINE,
                modules_line: str = MODULES_LINE):
    """(device_ops, device_modules, host_spans) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    dev_ops: Dict[int, List[Event]] = {}
    dev_mods: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = device_plane.match(plane.name)
        if m:
            d = int(m.group(1))
            for line in plane.lines:
                if line.name == ops_line:
                    dev_ops.setdefault(d, []).extend(
                        (_op_name(e), e.start_ns, e.duration_ns)
                        for e in line.events)
                elif line.name == modules_line:
                    dev_mods.setdefault(d, []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return dev_ops, dev_mods, spans

