"""Device time by ``jax.named_scope``: what share of a jitted program's
traced device time ran under given scopes.

The profiler's events of the device's "XLA Ops" line carry an operation's
HLO text without its metadata (read on a v5e, PR 28: the only statistics
are offsets and durations), so the trace alone does not know a scope. The
compiled program does: every instruction of its text has
``metadata={op_name="jit(decode_block)/.../raytpu.moe.experts/..."}``.
``scope_seconds`` therefore takes the trace AND the compiled texts of the
programs of interest (the replica compiles them again after the window;
the compile cache answers): an operation is attributed to the program
whose execution ("XLA Modules" line) holds its start, its instruction
name is looked up in that program's text, and the first
``raytpu.<layer>.<part>`` of its ``op_name`` is its label; a loop or a
branch is not counted beside its body. Operations the compiler names
itself lose their scope: the grouped products of ``lax.ragged_dot``
arrive as ``ragged-dot-*`` and are kept under that name, so that a metric
can list it beside a scope. Everything else is ``"-"``.

The reader ``scope_time_share`` reads ``facts["trace"]["scope_s"]``:
``{program: {"total": s, "<label>": s, ...}}``. Where the trace has none
(a runner or a program without scopes) it returns None and the metric is
left out of the line.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List

from benchmarks import trace
from benchmarks.common import reader

_SCOPE = re.compile(r"raytpu\.[a-z0-9_]+\.[a-z0-9_]+")
_COMPILER_NAMED = re.compile(r"^(ragged-dot)")
_NAMES = re.compile(r"^\s*(?:ROOT )?%(\S+) = ", re.M)
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)


def labels_of(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope, for the instructions of one compiled
    program that sit under a ``raytpu.*.*`` scope."""
    out = {}
    for name, op_name in _INSTRUCTION.findall(hlo_text):
        m = _SCOPE.search(op_name)
        if m:
            out[name] = m.group(0)
    return out


def scope_seconds(xplane_path: str, hlo_texts: Dict[str, List[str]]
                  ) -> Dict[str, Dict[str, float]]:
    """``hlo_texts``: program name (``decode_block``) -> the compiled
    texts of its variants (one per static argument: a block length, a
    prefill bucket). Instruction numbers differ between variants, so each
    traced module (its events share one id) is read with the variant
    whose instruction names cover most of the operations it ran. Only
    the named programs are reduced; their variants are summed."""
    from jax.profiler import ProfileData

    variants = {prog: [(set(_NAMES.findall(t)), labels_of(t)) for t in ts]
                for prog, ts in hlo_texts.items()}
    out: Dict[str, Dict[str, float]] = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not trace._DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        progs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in lines["XLA Modules"].events)
        starts = [p[0] for p in progs]
        ran: Dict[str, List] = {}  # module event name -> [(op, seconds)]
        for e in lines["XLA Ops"].events:
            if trace.is_container(e.name):
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i < 0 or e.start_ns >= progs[i][1] or (
                    trace.program_of(progs[i][2]) not in variants):
                continue
            ran.setdefault(progs[i][2], []).append(
                (e.name.partition(" = ")[0].lstrip("%"),
                 e.duration_ns * 1e-9))
        for module, ops in ran.items():
            prog = trace.program_of(module)
            seen = {name for name, _s in ops}
            _names, labels = max(variants[prog],
                                 key=lambda v: len(seen & v[0]))
            per = out.setdefault(prog, {"total": 0.0})
            for name, dur in ops:
                named = _COMPILER_NAMED.match(name)
                label = named.group(1) if named else labels.get(name, "-")
                per["total"] += dur
                per[label] = per.get(label, 0.0) + dur
    return out


@reader("scope_time_share")
def scope_time_share(facts, params):
    """100 x the seconds of ``program`` under labels that start with one of
    ``prefixes``, over all of that program's operation seconds."""
    per = ((facts.get("trace") or {}).get("scope_s") or {}).get(
        params["program"])
    if not per or not per.get("total"):
        return None
    under = sum(s for label, s in per.items() if label != "total"
                and label.startswith(tuple(params["prefixes"])))
    return 100.0 * under / per["total"]
