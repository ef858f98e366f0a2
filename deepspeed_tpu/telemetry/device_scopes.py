"""Device time by the program's own scopes.

A TPU trace names a device event by its HLO instruction (``fusion.412``)
and, on the v5e, carries no ``op_name``; the program names scopes on every
instruction it stages (flax's module path, ``trace.device_span``:
``layers_3/moe/route``, ``loss_head``, ``optimizer``).  The two meet in the
optimized HLO of the executable that ran, whose every instruction carries
``metadata={op_name="jit(step_fn)/jvp(Model)/layers_3/moe/route/..."}``.
The recompile watchdog keeps that executable (``recompile.watch(...,
staged=True)``, ``engine.compiled_step()``), so the map costs one parse of
its text, made when first asked for and kept beside the handle.

The arithmetic works on plain ``(name, start_ns, dur_ns)`` tuples so it
can be checked on a hand-made list; :func:`capture` turns a short
``jax.profiler`` session into those tuples with nothing but JAX.
``engine.profile_device_scopes`` is the operator's entry.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import shutil
import tempfile
import weakref
from typing import Callable, Dict, List, Sequence, Tuple

__all__ = ["instruction_scopes", "scope_of", "pass_of", "scope_table",
           "capture"]

Event = Tuple[str, float, float]            # instruction, start_ns, dur_ns

# an instruction's line of the HLO text, and the metadata further along it
# (line by line: one pattern over the whole text crawls through the
# megabytes of serialized kernel a Pallas custom call carries)
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r"metadata=\{op_name=\"([^\"]+)\"")
# segments of an op_name that say how JAX staged the instruction, not where
# the program was: a transform around something (``jit(f)``, ``jvp(M)``,
# ``transpose(jvp(M))``), flax's ``Module.method`` for a method that is not
# ``__call__``, remat's and control flow's own names, and the spec an
# einsum names itself by
_WRAPPER = re.compile(
    r".*\(.*\)$|.*\..*|.*->.*|checkpoint$|rematted_computation$|remat\d*$"
    r"|while$"
    r"|body$|cond$|closed_call$|core_call$|pjit$|branch_\d+_fun$"
    r"|custom_[jv][vj]p_call(_jaxpr)?$")
_INDEXED = re.compile(r"^(.*_)\d+$")
_NUMBERED = re.compile(r"[.\-_]\d+$")
_TOP_LEVEL = "(step)"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_scopes_of: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def instruction_scopes(compiled) -> Dict[str, str]:
    """``{instruction name: op_name}`` of ``compiled``'s optimized HLO,
    fused computations' inner instructions included; parsed once an
    executable.  Empty where the backend gives no text."""
    try:
        return _scopes_of[compiled]
    except KeyError:
        pass
    scopes = {}
    for line in (compiled.as_text() or "").splitlines():
        name = _INSTRUCTION.match(line)
        op = name and _OP_NAME.search(line, name.end())
        if op:
            scopes[name.group(1)] = op.group(1)
    _scopes_of[compiled] = scopes
    return scopes


def scope_of(op_name: str, depth: int = 3) -> str:
    """The first ``depth`` scopes of ``op_name``: its last segment (the
    primitive) and the transform wrappers dropped, layer indices folded
    (``layers_3`` → ``layers_*``), and a name that comes again taking the
    path back to where it first stood (a ``device_span("moe/route")``
    inside the module ``moe``: ``moe/moe/route`` → ``moe/route``)."""
    path: List[str] = []
    for seg in op_name.split("/")[:-1]:
        if _WRAPPER.match(seg):
            continue
        seg = _INDEXED.sub(r"\1*", seg)
        if seg in path:
            del path[path.index(seg):]
        path.append(seg)
    return "/".join(path[:depth]) or _TOP_LEVEL


def pass_of(op_name: str) -> str:
    """``forward``, ``recompute`` (a remat's second forward, inside the
    backward) or ``backward``."""
    if "transpose(" not in op_name:
        return "forward"
    return "recompute" if "/rematted_computation/" in op_name else "backward"


def _self_times(events: Sequence[Event]) -> List[Event]:
    """Each event's duration less what the events nested in it cover (a
    ``while`` or ``conditional`` spans its body's instructions on the
    same line)."""
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


def scope_table(events: Sequence[Event], scopes: Dict[str, str], steps: int,
                depth: int = 3, top: Sequence[str] = ()) -> dict:
    """Reduce one device's instruction events over ``steps`` steps:

    - ``scopes``: device ms a step by (scope, pass), largest first;
    - ``no_op_name``: the same for instructions ``scopes`` does not name,
      by kind (the name less its trailing number);
    - ``top``: for each wanted scope (a substring of the scope's path:
      ``"moe/route"``), its ten heaviest single instructions, each with
      its whole scope path and its ``op`` (the op_name's last segment:
      ``sort``, ``scatter-add``), and all of its time by ``op``;
    - ``device_ms_a_step``: their sum, the device's busy time a step.
    """
    by = collections.Counter()
    unnamed = collections.Counter()
    heavy = {want: collections.Counter() for want in top}
    by_op = {want: collections.Counter() for want in top}
    for name, _, dur in _self_times(events):
        op = scopes.get(name)
        if op is None:
            unnamed[_NUMBERED.sub("", name)] += dur
            continue
        scope, pass_ = scope_of(op, depth), pass_of(op)
        by[scope, pass_] += dur
        for want in top:
            if want in scope:
                primitive = op.rsplit("/", 1)[-1]
                heavy[want][name, pass_, scope_of(op, 99), primitive] += dur
                by_op[want][primitive] += dur
    ms = lambda ns: ns / steps / 1e6
    return {
        "steps": steps,
        "device_ms_a_step": ms(sum(by.values()) + sum(unnamed.values())),
        "scopes": [{"scope": s, "pass": p, "ms_a_step": ms(ns)}
                   for (s, p), ns in by.most_common()],
        "no_op_name": [{"kind": k, "ms_a_step": ms(ns)}
                       for k, ns in unnamed.most_common()],
        "top": {want: {
            "instructions": [{"instruction": n, "pass": p, "scope": s,
                              "op": o, "ms_a_step": ms(ns)}
                             for (n, p, s, o), ns
                             in heavy[want].most_common(10)],
            "ops": [{"op": o, "ms_a_step": ms(ns)}
                    for o, ns in by_op[want].most_common()]}
            for want in top},
    }


def capture(run: Callable[[], None]) -> Dict[int, List[Event]]:
    """``run()`` under a ``jax.profiler`` session of its own:
    ``{device index: [(instruction, start_ns, dur_ns)]}`` from the
    ``XLA Ops`` line of every ``/device:TPU:<n>`` plane (empty on a
    backend whose trace has none).  ``run`` fences its own work."""
    import jax
    from jax.profiler import ProfileData

    out = tempfile.mkdtemp(prefix="dstpu_device_scopes_")
    try:
        jax.profiler.start_trace(out)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                                 recursive=True))
        events: Dict[int, List[Event]] = {}
        for plane in ProfileData.from_file(files[-1]).planes if files else ():
            m = _DEVICE_PLANE.match(plane.name)
            if not m:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    events.setdefault(int(m.group(1)), []).extend(
                        (e.name.split(" = ")[0].lstrip("%"), e.start_ns,
                         e.duration_ns) for e in line.events)
        return events
    finally:
        shutil.rmtree(out, ignore_errors=True)
