"""From a profiler trace of the window to the numbers the per-layer
metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain record: for each device plane, the events of its XLA op line, and
the benchmark's own host spans (``chipbench.*``, from
``jax.profiler.TraceAnnotation``). ``reduce`` works on that record only,
so it is tested on a small recorded one.

- busy: the union of the op intervals inside the window span, per chip;
  ``busy_s`` is its mean over the chips.
- ops: the op events that start inside the window, per chip (mean).
- device_ops: the ops that took most self time (less the ops nested in
  them, such as a while loop's body), summed by name, mean over chips;
  a name is cut to ``NAME_CHARS``.
- idle_gaps: the longest stretches of the first chip with no op running,
  inside the window, each named by the innermost benchmark span that
  covers its middle (``host`` where none does).
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
WINDOW = "chipbench.window"
NAME_CHARS = 160


def capture(fn, logdir: str):
    """Run ``fn()`` under the profiler; returns (its result, the trace
    file)."""
    import jax
    os.makedirs(logdir, exist_ok=True)
    before = set(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    jax.profiler.start_trace(logdir)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    new = set(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)) - before
    if len(new) != 1:
        raise RuntimeError(f"expected one new trace under {logdir}: {new}")
    return out, new.pop()


def load(path: str) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, spans, lines = {}, [], {}
    for plane in pd.planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        if DEVICE_PLANE.match(plane.name):
            for ln in plane.lines:
                if ln.name == OP_LINE:
                    devices[plane.name] = [
                        (ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in ln.events]
        else:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)))
    return {"devices": devices, "spans": spans, "lines": lines}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """[name, start, end, self time] of (name, start, end) events: each
    event's length less that of the events directly nested in it."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= out[stack[-1]][2]:
            out[stack[-1]][3] -= e - s
        out.append([name, s, e, e - s])
        stack.append(len(out) - 1)
    return out


def reduce(rec: dict, top: int = 10) -> dict:
    wins = [(s, s + d) for n, s, d in rec["spans"] if n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(wins)}")
    w0, w1 = wins[0]
    if not rec["devices"]:
        raise ValueError("the trace holds no device op line "
                         f"({OP_LINE!r} on a {DEVICE_PLANE.pattern} plane)")
    busy, ops, by_name, first_union = [], [], {}, None
    for plane in sorted(rec["devices"]):
        clipped = [(max(s, w0), min(s + d, w1))
                   for _, s, d in rec["devices"][plane]
                   if s < w1 and s + d > w0]
        u = _union(clipped)
        if first_union is None:
            first_union = u
        busy.append(sum(e - s for s, e in u))
        ops.append(sum(1 for _, s, _ in rec["devices"][plane]
                       if w0 <= s < w1))
        inside = [(name[:NAME_CHARS], max(s, w0), min(s + d, w1))
                  for name, s, d in rec["devices"][plane]
                  if s < w1 and s + d > w0]
        for name, _, _, own in _self_times(inside):
            by_name[name] = by_name.get(name, 0.0) + own
    n = len(busy)
    gaps, prev = [], w0
    for s, e in first_union + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named_gaps = [(_label(rec["spans"], (a + b) / 2), (b - a) / 1e9)
                  for a, b in gaps]
    named_gaps.sort(key=lambda g: -g[1])
    dev_ops = sorted(((k, v / n / 1e9) for k, v in by_name.items()),
                     key=lambda kv: -kv[1])
    return {"window_s": (w1 - w0) / 1e9, "busy_s": sum(busy) / n / 1e9,
            "ops": sum(ops) / n, "chips": n,
            "device_ops": [list(x) for x in dev_ops[:top]],
            "idle_gaps": [list(x) for x in named_gaps[:top]]}


def _label(spans, t) -> str:
    best = None
    for name, s, d in spans:
        if name != WINDOW and s <= t <= s + d and (best is None
                                                   or d < best[1]):
            best = (name, d)
    return best[0] if best else "host"
