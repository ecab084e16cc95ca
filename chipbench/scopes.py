"""The program's named phases in a profiler trace: each device op's phase,
and the runtime's own work under each idle gap.

The program names the steps of Algorithm 1 with ``jax.named_scope``
(``core/asyrevel.py``, ``core/exchange.py``; the names are ``SCOPES``).
XLA keeps the name path in each instruction's ``op_name`` metadata, but
on a TPU the trace's op events carry no stat with it: an event holds
the instruction's HLO text, and the ``XLA Modules`` line above it names
the program it runs in. So each op's op_name is looked up in the
compiled HLO text of that program (``programs``: for each module name,
instruction name to op_name; ``chipbench/phases.py`` records them as the
run compiles or loads its programs). A fusion carries the op_name of its
root, so a direction draw that XLA fused into an update counts as
``zo_update``.

``load`` adds to ``trace_reduce.load``'s record of a trace, in step with
each device plane's op events, their op_names (``op_names``), and the
host events of the window other than the benchmark's own spans
(``host``). ``reduce`` returns ``trace_reduce.reduce``'s numbers
unchanged and adds:

- scope_s: the self time of the ops of each phase (the innermost of
  ``SCOPES`` in its op_name path), mean over chips; ops under none of
  them, and a ``while`` op's own time outside its body, count as
  ``unscoped``. Self time is ``trace_reduce``'s rule, so the phases and
  ``unscoped`` add up to the ops' self time. Empty where no op names a
  phase: a program without the scopes, or no programs recorded.
- within_s: for every identifier in any op's op_name path (a scope such
  as ``server_forward``, or a name XLA or JAX put there), the self time
  of the ops whose path holds it, each op counted once under each name
  its path holds, mean over chips. A scope nested in another counts in
  both, and a ``while`` op's own time counts under its path's names, so
  a phase's ``within_s`` is at least its ``scope_s``. A scope that a
  later program adds is read here with no change to ``SCOPES``.
- gap_events: the longest idle gaps of the first chip, as
  ``idle_gaps`` has them, each with the innermost host event that covers
  its middle (``-`` where none does): JAX's or the TPU runtime's work,
  such as the execute call or the transfer of a result to the host.
"""
from __future__ import annotations

import bisect
import re

from chipbench import trace_reduce as tr

SCOPES = ("ring_buffer", "party_forward", "exchange_up", "server_forward",
          "zo_perturb", "zo_update", "batch_gather")
UNSCOPED = "unscoped"
MODULE_LINE = "XLA Modules"
WHILE = re.compile(r"\bwhile\(")
_WORD = re.compile(r"[A-Za-z_]\w*")
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an op_name path such as
    ``jit(step)/zo_update/jvp(server_forward)/dot_general``, else
    ``UNSCOPED``."""
    names = [w for w in _WORD.findall(op_name or "") if w in SCOPES]
    return names[-1] if names else UNSCOPED


def op_names_of(hlo_text: str) -> dict:
    """Instruction name to op_name ("" where it has none) of a compiled
    module's HLO text; names are unique within a module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op.group(1) if op else ""
    return out


def add_program(programs: dict, name: str, hlo_text: str) -> None:
    """Enter a compiled module under its name. A name met twice keeps
    only the instructions on which both texts agree."""
    new = op_names_of(hlo_text)
    old = programs.get(name)
    programs[name] = new if old is None else {
        k: v for k, v in new.items() if old.get(k) == v}


def op_names_in(ops, modules, programs: dict) -> list:
    """The op_name of each (HLO text, start, length) op event: its
    instruction looked up in the module whose ``XLA Modules`` event
    (``name(fingerprint)``, start, length) covers the op's start."""
    mods = sorted((s, s + d, n.split("(")[0]) for n, s, d in modules)
    starts = [m[0] for m in mods]
    out = []
    for text, s, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        module = mods[i][2] if i >= 0 and s <= mods[i][1] else None
        instr = text.split(" ", 1)[0].lstrip("%")
        out.append(programs.get(module, {}).get(instr, ""))
    return out


def load(path: str, rec: dict, programs: dict) -> dict:
    """``rec``, ``trace_reduce.load``'s record of the trace at ``path``,
    with the op_names and host events added."""
    import jax
    w0, w1 = _window(rec)
    op_names, host = {}, []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: [(ev.name, float(ev.start_ns),
                                float(ev.duration_ns)) for ev in ln.events]
                     for ln in plane.lines
                     if ln.name in (tr.OP_LINE, MODULE_LINE)}
            op_names[plane.name] = op_names_in(
                lines.get(tr.OP_LINE, []), lines.get(MODULE_LINE, []),
                programs)
        else:
            for ln in plane.lines:
                for ev in ln.events:
                    s, d = float(ev.start_ns), float(ev.duration_ns)
                    if (not ev.name.startswith(tr.SPAN_PREFIX)
                            and s < w1 and s + d > w0):
                        host.append((ev.name, s, d))
    return dict(rec, op_names=op_names, host=host)


def _window(rec):
    (w0, w1), = [(s, s + d) for n, s, d in rec["spans"] if n == tr.WINDOW]
    return w0, w1


def reduce(rec: dict, top: int = 10) -> dict:
    out = tr.reduce(rec, top)
    w0, w1 = _window(rec)
    by_scope, within, scoped = {}, {}, False
    paths = {}     # op_name -> (its phase, the identifiers in its path)
    for plane, events in rec["devices"].items():
        names = rec.get("op_names", {}).get(plane, [""] * len(events))
        inside = [((name, op), max(s, w0), min(s + d, w1))
                  for (name, s, d), op in zip(events, names)
                  if s < w1 and s + d > w0]
        for (name, op), _, _, own in tr._self_times(inside):
            if op not in paths:
                paths[op] = (scope_of(op), set(_WORD.findall(op or "")))
            scope, words = paths[op]
            scoped |= scope != UNSCOPED
            if WHILE.search(name):
                scope = UNSCOPED
            by_scope[scope] = by_scope.get(scope, 0.0) + own
            for w in words:
                within[w] = within.get(w, 0.0) + own
    n = out["chips"]
    out["scope_s"] = ({k: v / n / 1e9 for k, v in by_scope.items()}
                      if scoped else {})
    out["within_s"] = {k: v / n / 1e9 for k, v in within.items()}
    out["gap_events"] = [
        [tr._label(rec["spans"], (a + b) / 2), (b - a) / 1e9,
         _innermost(rec.get("host", []), (a + b) / 2)]
        for a, b in _gaps(rec, w0, w1)[:top]]
    return out


def phase_ms(rec: dict, phase: str):
    """Device self time a round of the ops in ``phase`` (ms), from the
    run record's ``scope_s``: 0 for a phase with no op, None where the
    trace names no phase at all."""
    t = rec.get("trace")
    if not t or not t.get("scope_s") or not rec.get("rounds"):
        return None
    return 1e3 * t["scope_s"].get(phase, 0.0) / rec["rounds"]


def _gaps(rec, w0, w1):
    """The first chip's idle stretches in the window, longest first, in
    the order ``trace_reduce.reduce`` lists them."""
    first = sorted(rec["devices"])[0]
    busy = tr._union([(max(s, w0), min(s + d, w1))
                      for _, s, d in rec["devices"][first]
                      if s < w1 and s + d > w0])
    gaps, prev = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return sorted(gaps, key=lambda g: -(g[1] - g[0]) / 1e9)


def _innermost(events, t) -> str:
    best = None
    for name, s, d in events:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "-"
