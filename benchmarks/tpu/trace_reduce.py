"""From a profiler trace (``.xplane.pb``) to busy, idle and kernel time.

``reduce(path)`` reads the trace with ``jax.profiler.ProfileData`` and
returns, for the stretch that the host annotation ``window`` covers:

* ``window_s``: the stretch's length;
* ``busy_s``: the union of the intervals in which an operation ran on the
  device, averaged over the devices traced;
* ``ops``: per operation name, its device seconds and the names it goes
  by (the event's name, and its ``long_name``, ``hlo_op`` and ``tf_op``
  stats where present), so that a metric can match a kernel by pattern;
* ``gaps``: every idle gap, with the host annotation that covered most of
  it (``submit``, ``step``, ``request_states``, ``wait`` for the next
  arrival, ``train_step``, ``next_batch``), or ``(none)``.

On a TPU the device operations are the events of each ``/device:TPU:n``
plane's ``XLA Ops`` line.  On the CPU, where tests record small traces,
they are the host events that carry an ``hlo_op`` stat.
"""
from __future__ import annotations

import collections
import glob
import os

WINDOW = "window"
#: the benchmark's own host annotations around its calls into the program
ANNOTATIONS = ("submit", "step", "request_states", "wait", "train_step",
               "next_batch")
NAME_STATS = ("long_name", "hlo_op", "tf_op")


def find_trace(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _stats(event) -> dict:
    out = {}
    try:
        for k, v in event.stats:
            out[str(k)] = v
    except Exception:  # noqa: BLE001 - a stat of an unknown type
        pass
    return out


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_between(intervals, lo: float, hi: float) -> list:
    """Idle ``(start, end)`` stretches of ``[lo, hi]`` outside every interval."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def leaves(events) -> list:
    """The events that hold no other event of their line: an enclosing
    operation (a loop around its body's operations) would count its
    body's time twice in a table of operations."""
    out = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    return [ev for ev, nxt in zip(out, out[1:] + [None])
            if nxt is None or nxt[0] >= ev[1]
            or (nxt[0] == ev[0] and nxt[1] == ev[1])]


def _label(gap, annotations) -> str:
    best, best_overlap = "(none)", 0.0
    for name, s, e in annotations:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_overlap:
            best, best_overlap = name, ov
    return best


def reduce(path: str, *, platform: str = "tpu") -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_events: dict[str, list] = collections.defaultdict(list)
    annotations, window = [], None
    for plane in pd.planes:
        on_device = plane.name.startswith("/device:TPU:") and \
            "Core" not in plane.name
        for line in plane.lines:
            for ev in line.events:
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                if on_device and platform == "tpu":
                    if line.name == "XLA Ops":
                        device_events[plane.name].append(
                            (start, end, ev.name, _stats(ev)))
                    continue
                if not plane.name.startswith("/host:"):
                    continue
                st = _stats(ev) if platform != "tpu" else {}
                if platform != "tpu" and "hlo_op" in st:
                    device_events["cpu"].append((start, end, ev.name, st))
                elif ev.name == WINDOW:
                    window = (start, end)
                elif ev.name in ANNOTATIONS:
                    annotations.append((ev.name, start, end))
    if window is None:
        raise ValueError(f"{path}: no '{WINDOW}' annotation on the host")
    if not device_events:
        raise ValueError(f"{path}: no device operations in the trace")
    lo, hi = window
    ops: dict[str, dict] = {}
    busy, gaps = [], []
    for plane, events in device_events.items():
        inside = [(max(s, lo), min(e, hi), n, st) for s, e, n, st in events
                  if e > lo and s < hi]
        iv = [(s, e) for s, e, _, _ in inside]
        busy.append(union_seconds(iv))
        gaps.extend(gaps_between(iv, lo, hi))
        for s, e, name, st in leaves(inside):
            rec = ops.setdefault(name, {"seconds": 0.0, "count": 0,
                                        "names": [name]})
            rec["seconds"] += e - s
            rec["count"] += 1
            for k in NAME_STATS:
                v = st.get(k)
                if isinstance(v, str) and v not in rec["names"]:
                    rec["names"].append(v)
    inside_ann = [(n, s, e) for n, s, e in annotations if e > lo and s < hi]
    labelled = [(e - s, _label((s, e), inside_ann)) for s, e in gaps]
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "n_devices": len(busy),
        "ops": ops,
        "gaps": sorted(labelled, reverse=True),
    }


def kernel_seconds(reduced: dict, patterns) -> float:
    """Device seconds of the operations any of whose names matches one of
    ``patterns`` (regular expressions), averaged over the devices."""
    import re
    rx = [re.compile(p) for p in patterns]
    total = sum(rec["seconds"] for rec in reduced["ops"].values()
                if any(r.search(n) for r in rx for n in rec["names"]))
    return total / reduced["n_devices"]


def idle_percent(record: dict) -> float:
    """Share of the traced stretch in which no operation ran on the device:
    the reader of every ``idle_share.<cell kind>`` metric."""
    t = record["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(((n, r["seconds"] / reduced["n_devices"])
                  for n, r in reduced["ops"].items()),
                 key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[label, s] for s, label in reduced["gaps"][:top]]}
