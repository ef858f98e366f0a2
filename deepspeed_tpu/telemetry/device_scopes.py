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

The same parse keeps a ledger of the executable's collectives
(:func:`collective_ledger`): the optimized HLO is the only place a ZeRO
step's gathers exist (``parallel/zero.py scatter_grads``), so what a step
moves over the links, how often and for which module is read there, and
:func:`scope_table` books the device time of those instructions beside it.

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
import time
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import registry as _registry

__all__ = ["instruction_scopes", "collective_ledger", "ledger_totals",
           "record_collectives", "spans_devices", "scope_of", "pass_of",
           "scope_table", "capture"]

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
_NO_OP_NAME = "(no op_name)"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_parsed_of: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

# the opcode of an instruction's line: the first ``word(`` behind the end
# of its result type (``]``, a layout's ``}`` or a tuple's ``)``), so the
# search stops near the head of the line however long its tail is
_OPCODE = re.compile(r"[\]})] ([a-z][a-z\-]*)\(")
_COMPUTATION = re.compile(r"(ENTRY )?%?([\w.\-]+) \(.*\{$")
# the collective kinds; a ``-start`` is folded into its base and names
# its ``-done``.  The v5e's compiler (looked at by hand, PR 50) writes an
# asynchronous collective as a pair of fusions, ``async-collective-start``
# and ``async-collective-done``, each of whose fused computations holds
# the same collective (one ``channel_id``) in front of a custom call
_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
          "collective-permute")
_CALLERS = ("fusion", "call", "while", "conditional", "async-start",
            "async-done")
_CALLEES = re.compile(
    r"(?:calls|body|to_apply|true_computation|false_computation)=%?"
    r"([\w.\-]+)|branch_computations=\{([^}]*)\}")
_CONDITION = re.compile(r"condition=%?([\w.\-]+)")
_TRIP_COUNT = re.compile(r"known_trip_count\W+n\W+(\d+)")
_LESS_THAN = re.compile(r"[ ,]%?([\w.\-]+)\), direction=LT")
_CONSTANT = re.compile(r"%?([\w.\-]+) = [su]\d+\[\]\S* constant\((\d+)\)")
_CHANNEL = re.compile(r"channel_id=(\d+)")
_OPERAND = re.compile(r"\(%?([\w.\-]+)")
_GROUPS = re.compile(
    r"replica_groups=(?:\{\{([\d,]*)\}|\[\d+,(\d+)\]<=\[|\{\})")
_PARTITIONS = re.compile(r"(?:num_partitions|replica_count)=(\d+)")
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/")
_TYPE_TOKEN = re.compile(r"[()]|([a-z]\w*)\[([\d,<= ]*)\]")
_BITS = re.compile(r"[a-z]+(\d+)")


def _element_bytes(type_text: str) -> List[int]:
    """Bytes of each top-level element of an HLO result type: one element
    for an array, and a tuple inside a tuple counts as one."""
    text = _LAYOUT.sub("", type_text).strip()
    out: List[int] = []
    depth = 0
    for tok in _TYPE_TOKEN.finditer(text):
        if tok.group(0) == "(":
            depth += 1
            if depth == 2:
                out.append(0)
        elif tok.group(0) == ")":
            depth -= 1
        else:
            dtype, dims = tok.groups()
            bits = _BITS.match(dtype)       # token, opaque: nothing moves
            n = int(bits.group(1)) if bits else 8 * (dtype == "pred")
            for d in dims.replace("<=", "").split(","):
                n *= int(d) if d.strip() else 1
            if depth >= 2:
                out[-1] += n // 8
            else:
                out.append(n // 8)
    return out


def _received(op: str, result: int, n: Optional[int]) -> float:
    """Bytes ONE device receives for a collective whose per-device result
    holds ``result`` bytes, over a group of ``n``."""
    if op == "collective-permute":
        return float(result)
    if n <= 1:
        return 0.0
    if op == "reduce-scatter":
        return float(result) * (n - 1)
    if op == "all-reduce":
        return 2.0 * result * (n - 1) / n
    return float(result) * (n - 1) / n      # all-gather, all-to-all


def _fused(inner: List[dict], fusion: str, op_name: Optional[str],
           type_text: str) -> None:
    """``inner``, the collectives of a fusion's computation, as the trace
    shows them: under the fusion's name, and under its ``op_name`` where
    they have none.  An all-reduce whose fusion keeps a ``1/n`` of it (the
    v5e's ``all-reduce-scatter``: the all-reduce and the slice of it in
    one instruction) is the reduce-scatter it runs as."""
    for rec in inner:
        rec["instruction"] = fusion
        if rec["op_name"] is None and op_name:
            rec.update(op_name=op_name, scope=scope_of(op_name),
                       **{"pass": pass_of(op_name)})
    if len(inner) == 1 and inner[0]["op"] == "all-reduce":
        rec, kept = inner[0], sum(_element_bytes(type_text))
        if rec["n"] > 1 and kept * rec["n"] == rec["bytes"]:
            rec.update(op="reduce-scatter", bytes=kept,
                       recv_bytes=_received("reduce-scatter", kept,
                                            rec["n"]))


def _loop_trips(lines: Sequence[str], line: str, spans: dict) -> int:
    """How often a ``while`` runs its body: what the compiler states
    (``known_trip_count``), else the ``N`` of a condition ``i < N`` as
    ``lax.scan`` and ``fori_loop`` write it (from 0 by 1), else 1."""
    known = _TRIP_COUNT.search(line)
    if known:
        return int(known.group(1))
    cond = _CONDITION.search(line)
    lo, hi = spans.get(cond.group(1), (0, 0)) if cond else (0, 0)
    constants, bound = {}, None
    for text in lines[lo:hi]:
        c = _CONSTANT.search(text)
        if c:
            constants[c.group(1)] = int(c.group(2))
        lt = _LESS_THAN.search(text)
        if lt:
            bound = lt.group(1)
    return constants.get(bound, 1)


def _parse(compiled):
    """One walk over ``compiled``'s optimized HLO, kept beside the handle:
    ``(scopes, ledger)`` of :func:`instruction_scopes` and
    :func:`collective_ledger`."""
    try:
        return _parsed_of[compiled]
    except KeyError:
        pass
    try:
        text = compiled.as_text() or ""
    except Exception:           # a backend that keeps no text
        text = ""
    parsed = _parse_text(text)
    _parsed_of[compiled] = parsed
    return parsed


def _parse_text(text: str):
    lines = text.splitlines()
    scopes: Dict[str, str] = {}
    # the collectives of each computation, those of the computations it
    # calls included (a callee stands before its caller in the text)
    held: Dict[str, List[dict]] = {}
    spans: Dict[str, Tuple[int, int]] = {}
    world = 1
    for m in _PARTITIONS.finditer(lines[0] if lines else ""):
        world *= int(m.group(1))
    comp, comp_start, entry = None, 0, None
    for i, line in enumerate(lines):
        name = _INSTRUCTION.match(line)
        if not name:
            head = _COMPUTATION.match(line)
            if head:
                comp, comp_start = head.group(2), i
                entry = comp if head.group(1) else entry
            elif line.startswith("}") and comp is not None:
                spans[comp] = (comp_start, i)
            continue
        op_name = _OP_NAME.search(line, name.end())
        if op_name:
            scopes[name.group(1)] = op_name.group(1)
        # a module of one partition holds no collective: nothing more
        opcode = world > 1 and _OPCODE.search(line, name.end())
        if not opcode:
            continue
        code = opcode.group(1)
        kind = next((k for k in _KINDS if code.startswith(k)
                     and code[len(k):] in ("", "-start", "-done")), None)
        if kind is not None:
            mine = held.setdefault(comp, [])
            if code.endswith("-done"):
                start = _OPERAND.search(line, opcode.end() - 1)
                for rec in mine:
                    if start and rec["instruction"] == start.group(1):
                        rec["done"] = name.group(1)
                continue
            sizes = _element_bytes(line[name.end():opcode.start() + 1])
            # a start's result is (operands, results[, contexts]): the
            # collective's own result is its second element
            result = sizes[1] if code.endswith("-start") and kind in (
                "all-gather", "collective-permute") and len(sizes) > 1 \
                else sum(sizes)
            groups = _GROUPS.search(line, opcode.end())
            n = None if kind == "collective-permute" else \
                world if not groups else \
                groups.group(1).count(",") + 1 if groups.group(1) is not None \
                else int(groups.group(2)) if groups.group(2) else world
            channel = _CHANNEL.search(line, opcode.end())
            op = op_name.group(1) if op_name else None
            mine.append({
                "instruction": name.group(1), "op": kind, "done": None,
                "n": n, "bytes": result,
                "recv_bytes": _received(kind, result, n), "times": 1,
                "scope": scope_of(op) if op else _NO_OP_NAME,
                "pass": pass_of(op) if op else "", "op_name": op,
                "channel": int(channel.group(1)) if channel else None})
        elif code in _CALLERS and held:
            callees = [c.strip().lstrip("%")
                       for m in _CALLEES.finditer(line, opcode.end())
                       for c in (m.group(1) or m.group(2)).split(",")]
            inner = [rec for c in callees for rec in held.get(c, ())]
            if not inner:
                continue
            if code == "while":
                trips = _loop_trips(lines, line, spans)
                for rec in inner:
                    rec["times"] *= trips
            elif code == "fusion":
                _fused(inner, name.group(1), op_name and op_name.group(1),
                       line[name.end():opcode.start() + 1])
            held.setdefault(comp, []).extend(inner)
    ledger: List[dict] = []
    by_channel: Dict[int, dict] = {}
    for rec in held.get(entry, ()):
        first = rec if rec["channel"] is None \
            else by_channel.setdefault(rec["channel"], rec)
        if first is rec:
            ledger.append(rec)
        elif first["instruction"] != rec["instruction"]:
            # one channel under two fusions: the asynchronous pair
            first["instruction"], first["done"] = sorted(
                (first["instruction"], rec["instruction"]),
                key=lambda fusion: "done" in fusion)
    for rec in ledger:
        del rec["channel"]
    return scopes, ledger


def instruction_scopes(compiled) -> Dict[str, str]:
    """``{instruction name: op_name}`` of ``compiled``'s optimized HLO,
    fused computations' inner instructions included; parsed once an
    executable.  Empty where the backend gives no text."""
    return _parse(compiled)[0]


def collective_ledger(compiled) -> List[dict]:
    """The collectives of ``compiled``'s optimized HLO, one record each,
    from the same parse as :func:`instruction_scopes`:

    - ``instruction``: its name on the trace's ``XLA Ops`` line (the
      fusion's, where the compiler fused it); ``done``: the instruction
      that waits for an asynchronous one, else ``None``;
    - ``op``: ``all-gather``, ``all-reduce``, ``reduce-scatter``,
      ``all-to-all``, ``collective-permute`` (``-start`` folded in);
    - ``n``: the size of its replica group (``None`` for a permute,
      which has pairs); ``bytes``: its per-device result;
      ``recv_bytes``: what ONE device receives for it
      (all-gather and all-to-all ``bytes x (n-1)/n``, reduce-scatter
      ``bytes x (n-1)``, all-reduce ``2 x bytes x (n-1)/n``, a permute
      ``bytes``);
    - ``times``: how often a step runs it (the trips of the loops around
      it, :func:`_loop_trips`);
    - ``scope``, ``pass``: :func:`scope_of` and :func:`pass_of` of its
      ``op_name``, so a gather is booked under the module that consumes
      the shard; ``(no op_name)`` where the compiler wrote none.

    Empty for an executable of one device, which holds none."""
    return _parse(compiled)[1]


def spans_devices(compiled) -> int:
    """How many devices ``compiled`` runs on, from the shardings it was
    compiled for (never from its text)."""
    import jax

    shardings = jax.tree_util.tree_leaves(
        (compiled.input_shardings, compiled.output_shardings))
    return len(shardings[0].device_set) if shardings else 1


def record_collectives(compiled, site: str, registry=None) -> None:
    """Publish the ledger of an executable of MORE THAN ONE device as
    gauges: ``step_collectives{site, op}`` (instructions a step),
    ``step_collective_recv_bytes{site, op}`` (bytes one device receives a
    step) and ``step_collective_parse_seconds{site}`` (what fetching and
    parsing the text cost, once, when the executable was made).  An
    executable of one device holds no collective and is never asked for
    its text, which for a large step is megabytes."""
    try:
        if spans_devices(compiled) <= 1:
            return
    except Exception:
        return
    t0 = time.perf_counter()
    totals = ledger_totals(collective_ledger(compiled))
    seconds = time.perf_counter() - t0
    reg = registry or _registry.get_registry()
    reg.gauge("step_collective_parse_seconds",
              "seconds to fetch and parse the executable's HLO text",
              labelnames=("site",)).labels(site=site).set(seconds)
    for op, (count, recv) in totals.items():
        reg.gauge("step_collectives",
                  "collective instructions a step of the executable",
                  labelnames=("site", "op")).labels(site=site, op=op
                                                    ).set(float(count))
        reg.gauge("step_collective_recv_bytes",
                  "bytes one device receives a step, by the executable's "
                  "collectives", labelnames=("site", "op")
                  ).labels(site=site, op=op).set(recv)


def ledger_totals(ledger: Sequence[dict]) -> Dict[str, Tuple[int, float]]:
    """``{op: (instructions a step, bytes one device receives a step)}``."""
    out: Dict[str, Tuple[int, float]] = {}
    for rec in ledger:
        count, recv = out.get(rec["op"], (0, 0.0))
        out[rec["op"]] = (count + rec["times"],
                          recv + rec["times"] * rec["recv_bytes"])
    return out


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
                depth: int = 3, top: Sequence[str] = (),
                ledger: Sequence[dict] = ()) -> dict:
    """Reduce one device's instruction events over ``steps`` steps:

    - ``scopes``: device ms a step by (scope, pass), largest first;
    - ``no_op_name``: the same for instructions ``scopes`` does not name,
      by kind (the name less its trailing number);
    - ``top``: for each wanted scope (a substring of the scope's path:
      ``"moe/route"``), its ten heaviest single instructions, each with
      its whole scope path and its ``op`` (the op_name's last segment:
      ``sort``, ``scatter-add``), and all of its time by ``op``;
    - ``device_ms_a_step``: their sum, the device's busy time a step;
    - ``collectives``: device ms a step of ``ledger``'s instructions by
      (``op``, scope, pass), largest first, a ``-done``'s wait booked to
      its start's row, with the row's instructions and MiB received a
      step beside it; ``collective_ms_a_step``: their sum.  An
      asynchronous collective is a short start and a done that lasts as
      long as the device waited, and a synchronous one holds the line
      for its whole length: this time is the time that was NOT hidden
      under compute.  (The same events stay in ``scopes``.)
    """
    by = collections.Counter()
    unnamed = collections.Counter()
    heavy = {want: collections.Counter() for want in top}
    by_op = {want: collections.Counter() for want in top}
    row_of, rows = {}, {}
    for rec in ledger:
        key = (rec["op"], scope_of(rec["op_name"], depth)
               if rec.get("op_name") else rec["scope"], rec["pass"])
        row = rows.setdefault(key, {"ns": 0.0, "instructions": 0,
                                    "recv_bytes": 0.0})
        row["instructions"] += rec["times"]
        row["recv_bytes"] += rec["times"] * rec["recv_bytes"]
        row_of[rec["instruction"]] = row_of[rec["done"]] = row
    row_of.pop(None, None)
    for name, _, dur in _self_times(events):
        if name in row_of:
            row_of[name]["ns"] += dur
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
        "collective_ms_a_step": ms(sum(r["ns"] for r in rows.values())),
        "collectives": [
            {"op": o, "scope": s, "pass": p, "ms_a_step": ms(r["ns"]),
             "instructions": r["instructions"],
             "recv_mib_a_step": r["recv_bytes"] / 2**20}
            for (o, s, p), r in sorted(rows.items(),
                                       key=lambda kv: -kv[1]["ns"])],
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
