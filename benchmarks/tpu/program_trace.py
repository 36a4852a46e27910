"""The program's own spans in a profiler trace, and what they explain.

The program's tracer writes every span that begins and ends inside one
host call into the profiler's trace as a host event named
``repro.<span>`` (the engine step and its phases ``admit``, ``dispatch``,
``sync``, ``emit``; ``prefill``; ``compile``), and its jitted programs
name their phases with ``jax.named_scope``.  From a trace, for the
stretch that the host annotation ``window`` covers, ``reduce(path)``
returns:

* ``program_spans``: each ``repro.*`` host event inside the stretch, as
  ``{"name", "start", "end", "attrs"}`` (the span name without its
  prefix; the span's begin attributes);
* ``program_gaps``: every idle gap of the device, as ``(seconds,
  label)``, labelled by the shortest program span that covers most of
  it, or ``(none)``; sorted longest first;
* ``program_scopes``: device seconds by the operations' ``op_name``
  (``jit(f)/sentinel/reduce_and``), averaged over the devices.

A device operation's event names its HLO instruction, not its
``op_name``.  The profiler stores each program's optimized HLO module in
the trace's ``/host:metadata`` plane; ``hlo_op_names`` reads every
instruction's ``op_name`` from there, and each operation is matched to
its program by the ``XLA Modules`` event that holds it (on the CPU, by
its ``program_id`` stat).  ``scope_seconds`` sums ``program_scopes`` by
scope name.  A trace of a program that writes no such spans or scopes
gives empty lists and zero seconds.
"""
from __future__ import annotations

import bisect
import collections
import re

import trace_reduce

PREFIX = "repro."
METADATA_PLANE = "/host:metadata"
HLO_PROTO = "Hlo Proto"


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """``(field number, value)`` of a protobuf message's fields, in wire
    order: an int for a varint, a view of the bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not handled")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _ids(value) -> list:
    """A repeated int64 field: one varint, or a packed run of them."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _module_op_names(module) -> dict:
    """Instruction name -> ``op_name`` of an ``HloModuleProto``
    (computations 3 > id 5, root_id 6, instructions 2 > name 1, metadata
    7 > op_name 2, id 35, operand_ids 36, called_computation_ids 38).

    An instruction that XLA made and left without an ``op_name`` (a
    fusion, a copy) takes that of its called computation's root, else of
    its first operand that has one: a fusion is named by its root, as
    XLA names it, and a copy by what it copies."""
    insts, roots = {}, {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        comp_id = root = None
        for g, inst in _fields(comp):
            if g == 5:
                comp_id = inst
            elif g == 6:
                root = inst
            elif g == 2:
                rec = {"name": "", "op": "", "operands": [], "called": []}
                inst_id = None
                for h, v in _fields(inst):
                    if h == 1:
                        rec["name"] = _text(v)
                    elif h == 7:
                        rec["op"] = next((_text(m) for k, m in _fields(v)
                                          if k == 2), "")
                    elif h == 35:
                        inst_id = v
                    elif h == 36:
                        rec["operands"] += _ids(v)
                    elif h == 38:
                        rec["called"] += _ids(v)
                insts[inst_id] = rec
        roots[comp_id] = root
    memo: dict = {}

    def op_name(i, depth=0) -> str:
        if i in memo:
            return memo[i]
        memo[i] = ""                          # a cycle resolves to nothing
        rec = insts.get(i)
        if rec is None:
            return ""
        out = rec["op"]
        if not out and depth < 64:
            for nxt in [roots.get(c) for c in rec["called"]] + \
                    rec["operands"]:
                out = op_name(nxt, depth + 1)
                if out:
                    break
        memo[i] = out
        return out

    return {rec["name"]: op_name(i) for i, rec in insts.items()
            if op_name(i)}


def hlo_op_names(path: str) -> dict:
    """Program id -> {instruction name -> ``op_name``}, from the HLO
    modules in the trace's ``/host:metadata`` plane (an ``XSpace``:
    planes 1 > name 2, event_metadata 4, stat_metadata 5; each event
    metadata's ``Hlo Proto`` stat holds an ``HloProto``, whose field 1
    is the module)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if next((_text(v) for g, v in fields if g == 2), "") \
                != METADATA_PLANE:
            continue
        stat_names = {}
        for g, entry in fields:
            if g == 5:
                value = dict(_fields(entry)).get(2, b"")
                meta = dict(_fields(value))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        for g, entry in fields:
            if g != 4:
                continue
            entry = dict(_fields(entry))
            for h, stat in _fields(entry.get(2, b"")):
                stat = dict(_fields(stat)) if h == 5 else {}
                if stat_names.get(stat.get(1)) == HLO_PROTO and 6 in stat:
                    module = dict(_fields(stat[6])).get(1, b"")
                    out[entry.get(1, 0)] = _module_op_names(module)
    return out


def _program_id(name: str):
    m = re.search(r"\((\d+)\)$", name)
    return int(m.group(1)) if m else None


def _label(gap, spans) -> str:
    """The shortest span that covers more than half of ``gap``."""
    best, best_len = "(none)", None
    half = 0.5 * (gap[1] - gap[0])
    for sp in spans:
        overlap = min(sp["end"], gap[1]) - max(sp["start"], gap[0])
        length = sp["end"] - sp["start"]
        if overlap > half and (best_len is None or length < best_len):
            best, best_len = sp["name"], length
    return best


def reduce(path: str, *, platform: str = "tpu") -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    # per device: (start, end, program id, instruction name) of each op
    device: dict[str, list] = collections.defaultdict(list)
    modules: dict[str, list] = collections.defaultdict(list)
    spans, window = [], None
    for plane in pd.planes:
        on_device = plane.name.startswith("/device:TPU:") and \
            "Core" not in plane.name
        for line in plane.lines:
            for ev in line.events:
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                if on_device and platform == "tpu":
                    if line.name == "XLA Ops":
                        device[plane.name].append(
                            (start, end, None,
                             ev.name.split(" ", 1)[0].lstrip("%")))
                    elif line.name == "XLA Modules":
                        modules[plane.name].append(
                            (start, end, _program_id(ev.name)))
                    continue
                if not plane.name.startswith("/host:"):
                    continue
                if ev.name == trace_reduce.WINDOW:
                    window = (start, end)
                elif ev.name.startswith(PREFIX):
                    spans.append({"name": ev.name[len(PREFIX):],
                                  "start": start, "end": end,
                                  "attrs": trace_reduce._stats(ev)})
                elif platform != "tpu":
                    st = trace_reduce._stats(ev)
                    if "hlo_op" in st:
                        device["cpu"].append((start, end,
                                              st.get("program_id"),
                                              str(st["hlo_op"])))
    if window is None:
        raise ValueError(f"{path}: no '{trace_reduce.WINDOW}' annotation "
                         f"on the host")
    lo, hi = window
    inside = sorted((sp for sp in spans if sp["end"] > lo and sp["start"] < hi),
                    key=lambda sp: sp["start"])
    op_names = hlo_op_names(path)
    # an instruction name that only one program holds needs no program
    count = collections.Counter(i for m in op_names.values() for i in m)
    unique = {i: op for m in op_names.values() for i, op in m.items()
              if count[i] == 1}
    gaps, scopes = [], collections.defaultdict(float)
    for plane, events in device.items():
        inside_ops = [(max(s, lo), min(e, hi), pid, inst)
                      for s, e, pid, inst in events if e > lo and s < hi]
        gaps.extend(trace_reduce.gaps_between(
            [(s, e) for s, e, _, _ in inside_ops], lo, hi))
        held = sorted(modules.get(plane, []))
        starts = [m[0] for m in held]
        for s, e, pid, inst in trace_reduce.leaves(inside_ops):
            if pid is None and held:        # the module event holding it
                k = bisect.bisect_right(starts, s) - 1
                if k >= 0 and held[k][1] >= e:
                    pid = held[k][2]
            names = op_names.get(pid)
            op_name = unique.get(inst) if names is None else names.get(inst)
            if op_name:
                scopes[op_name] += (e - s) / len(device)
    return {"program_spans": inside,
            "program_gaps": sorted(((e - s, _label((s, e), inside))
                                    for s, e in gaps), reverse=True),
            "program_scopes": dict(scopes)}


def scope_seconds(reduced: dict, scope: str) -> float:
    """Device seconds of the operations whose ``op_name`` holds ``scope``
    as one of its parts (``jit(f)/sentinel/...``, or wrapped by a
    transformation as in ``transpose(jvp(lm_head_loss))/...``)."""
    rx = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    return sum(sec for name, sec in reduced.get("program_scopes", {}).items()
               if rx.search(name))
