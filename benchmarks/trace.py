"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
readers use: device busy time, idle gaps with an owner, per-operation
sums, and the executions of each jitted program.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. The
arithmetic (``union``, ``gaps``, ``pair_after``) works on plain tuples so
that it is tested without a trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]  # start, end, seconds on the trace's clock

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] that ``busy`` (merged) leaves."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def gaps_by_owner(idle: List[Interval],
                  owner: Callable[[float, float], str]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for a, b in idle:
        name = owner(a, b)
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def start(trace_dir: str) -> None:
    """Starts the profiler WITHOUT its Python tracer: that one hooks every
    call of every thread, which slows a replica with a hundred request
    threads far more than the device trace and the ``bench.`` marks need
    (one traced run of PR 24 stalled ~10 s and failed 8 requests with it
    on)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stats(event) -> Dict:
    try:
        return {k: v for k, v in event.stats}
    except Exception:  # noqa: BLE001 — a stat the reader cannot decode
        return {}


def load(path: str, mark_prefix: str = "bench.",
         rehearsal: bool = False) -> Dict:
    """``{"devices": {id: {"ops": [...], "programs": [...]}}, "marks":
    [...]}``; every event is ``{"name", "start", "end"}`` in seconds, a
    mark also has its ``stats``. ``ops`` are the device's operations (line
    "XLA Ops"), ``programs`` the executions of jitted programs (line "XLA
    Modules"), ``marks`` the host spans this benchmark wrote with
    ``TraceAnnotation`` (names starting ``bench.``).

    ``rehearsal``: a trace taken on the host has no device plane; the
    XLA CPU client's threads then stand in for the operations and the
    ``PjitFunction(...)`` calls for the programs, so that the control flow
    is walked. Nothing read that way is ever reported."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Dict]]] = {}
    marks: List[Dict] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(
                int(m.group(1)), {"ops": [], "programs": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "programs"}.get(
                    line.name)
                if key is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    ev = {"name": e.name, "start": s,
                          "end": s + e.duration_ns * 1e-9}
                    dev[key].append(ev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if rehearsal and e.duration_ns > 0 and not \
                            e.name.startswith(("$", mark_prefix)):
                        dev = devices.setdefault(
                            0, {"ops": [], "programs": []})
                        s = e.start_ns * 1e-9
                        prog = e.name.startswith("PjitFunction(")
                        dev["programs" if prog else "ops"].append({
                            "name": "jit_" + e.name[13:-1] if prog
                            else e.name,
                            "start": s, "end": s + e.duration_ns * 1e-9})
                    if e.name.startswith(mark_prefix):
                        s = e.start_ns * 1e-9
                        marks.append({
                            "name": e.name, "start": s,
                            "end": s + e.duration_ns * 1e-9,
                            "stats": _stats(e),
                        })
    marks.sort(key=lambda m: m["start"])
    return {"devices": devices, "marks": marks}


_CONTAINERS = ("while", "conditional", "call")


def short_op(name: str) -> str:
    """An operation's event is named by its whole HLO text,
    ``%fusion.193 = bf16[8,16,256]{...} fusion(...)``: keep the
    instruction's name and its result type, ``fusion.193:bf16[8,16,256]``
    (``:tuple`` for a tuple)."""
    head, sep, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head
    if rest.startswith("("):
        return head + ":tuple"
    return head + ":" + rest.split("{", 1)[0].split(" ", 1)[0]


def is_container(name: str) -> bool:
    """A loop or branch holds its body's operations as events of their
    own: its time is theirs, and is not counted a second time."""
    return name.lstrip("%").startswith(_CONTAINERS)


def program_of(name: str) -> str:
    """``jit_decode_block(123)`` -> ``decode_block``."""
    name = name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def reduce(loaded: Dict, owner_for: Optional[Callable] = None) -> Dict:
    """Per device and averaged: the window (first mark or operation to the
    last), busy seconds (union of operations), idle gaps by owner, the ten
    operations with most time, program executions, collective time."""
    devs = loaded["devices"]
    if not devs:
        raise ValueError("the trace has no /device:TPU plane")
    marks = loaded["marks"]
    window_marks = [m for m in marks if m["name"] == "bench.window"]
    if window_marks:
        lo, hi = window_marks[0]["start"], window_marks[-1]["end"]
    else:
        every = [e for d in devs.values() for e in d["ops"]]
        lo = min(e["start"] for e in every)
        hi = max(e["end"] for e in every)
    per_dev = []
    op_sums: Dict[str, float] = {}
    for dev_id in sorted(devs):
        d = devs[dev_id]
        programs = [p for p in d["programs"]
                    if p["end"] > lo and p["start"] < hi]
        programs.sort(key=lambda p: p["start"])
        ops = [o for o in d["ops"] if o["end"] > lo and o["start"] < hi]
        busy = union(clip(((o["start"], o["end"]) for o in ops), lo, hi))
        idle = gaps(busy, lo, hi)
        owner = owner_for(marks) if owner_for else (lambda a, b: "unknown")
        starts = [p["start"] for p in programs]
        collective = 0.0
        for o in ops:
            if is_container(o["name"]):
                continue
            i = bisect.bisect_right(starts, o["start"]) - 1
            prog = program_of(programs[i]["name"]) if i >= 0 and \
                o["start"] < programs[i]["end"] else "?"
            key = f"{prog}/{short_op(o['name'])}"
            dur = min(o["end"], hi) - max(o["start"], lo)
            op_sums[key] = op_sums.get(key, 0.0) + dur
            if _COLLECTIVE.search(o["name"].partition(" = ")[0]):
                collective += dur
        per_dev.append({
            "busy_s": total(busy),
            "idle_by_owner": gaps_by_owner(idle, owner),
            "programs": programs,
            "ops": ops,
            "collective_s": collective,
        })
    n = len(per_dev)
    idle_owner: Dict[str, float] = {}
    for d in per_dev:
        for k, v in d["idle_by_owner"].items():
            idle_owner[k] = idle_owner.get(k, 0.0) + v / n
    top = sorted(op_sums.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": hi - lo,
        "lo": lo,
        "hi": hi,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "collective_s": sum(d["collective_s"] for d in per_dev) / n,
        "device_ops": [[k, v / n] for k, v in top],
        "idle_gaps": sorted(([k, v] for k, v in idle_owner.items()),
                            key=lambda kv: -kv[1])[:10],
        "per_device": per_dev,
        "marks": marks,
    }


def op_table(loaded: Dict, device: int = 0) -> str:
    """Every distinct operation of one device: calls, seconds, and the
    whole text of its first call. For reading a new trace by hand."""
    rows: Dict[str, List] = {}
    for o in loaded["devices"][device]["ops"]:
        r = rows.setdefault(short_op(o["name"]), [0, 0.0, o["name"]])
        r[0] += 1
        r[1] += o["end"] - o["start"]
    return "\n".join(
        f"{n:6d} {t:10.6f}s {k}    {text[:700]}"
        for k, (n, t, text) in sorted(rows.items(),
                                      key=lambda kv: -kv[1][1]))


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and their first events: read this once by hand
    before trusting the reduction on a new device."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            for e in evs[:limit]:
                out.append(f"    {e.name!r} start_ns={e.start_ns} "
                           f"dur_ns={e.duration_ns} stats={_stats(e)}")
    return "\n".join(out)
