"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
per-operation device time and idle gaps labelled by the host span that
was open in them.

The reduction works on plain event lists, so a test can feed it a small
recorded or hand-made trace:

  device ops     ``(name, start_ns, dur_ns)`` of one chip's "XLA Ops"
                 line. A ``while`` op spans its body's ops, so busy time
                 is the union of intervals and an op's own time is its
                 duration less that of the ops inside it.
  modules        the same for the chip's "XLA Modules" line: one event
                 per run of a compiled program, ``jit_<fn>(<id>)``.
  host spans     the benchmark's own ``TraceAnnotation`` spans.

Host and device events share the trace's clock (nanoseconds from the
start of the profiling session).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start ns, duration ns

WINDOW_SPAN = "bench.window"


def op_name(event_name: str) -> str:
    """``"%fusion.3 = f32[..] fusion(..)"`` -> ``"fusion.3"``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``"jit_step(8780964786730425824)"`` -> ``"jit_step"``."""
    return event_name.split("(", 1)[0]


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(ops: Sequence[Event]) -> List[int]:
    """Each op's duration less the durations of the ops nested in it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [d for _, _, d in ops]
    stack: List[int] = []
    for i in order:
        s = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return own


@dataclasses.dataclass
class Chip:
    """One chip's events clipped to the window."""

    busy: List[Tuple[int, int]]                  # merged op intervals
    op_ns: Dict[str, int]                        # "<module>/<op>" -> own ns
    modules: List[Event]

    def busy_ns(self, a: int, b: int) -> int:
        """Busy nanoseconds inside ``[a, b)``."""
        i = max(bisect.bisect_right(self.busy, (a, a)) - 1, 0)
        total = 0
        for s, e in self.busy[i:]:
            if s >= b:
                break
            total += max(0, min(e, b) - max(s, a))
        return total


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]
    chips: List[Chip]
    spans: List[Event]                           # host spans in the window

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def busy_ns(self, a: Optional[int] = None, b: Optional[int] = None
                ) -> float:
        """Busy time in ``[a, b)`` (default: the window), averaged over
        the chips."""
        a = self.window[0] if a is None else a
        b = self.window[1] if b is None else b
        return sum(c.busy_ns(a, b) for c in self.chips) / len(self.chips)

    def op_ns(self) -> Dict[str, float]:
        """Own time of each ``<module>/<op>``, averaged over the chips."""
        total: Dict[str, float] = collections.Counter()
        for c in self.chips:
            for k, v in c.op_ns.items():
                total[k] += v / len(self.chips)
        return dict(total)

    def module_events(self, base: str) -> List[Event]:
        """Runs of the compiled program ``base`` (e.g. ``"jit_step"``) on
        the first chip."""
        return [m for m in self.chips[0].modules if module_name(m[0]) == base]

    def span_events(self, name: str) -> List[Event]:
        return [s for s in self.spans if s[0] == name]

    def gaps(self) -> Dict[str, Tuple[float, int]]:
        """Idle time of the first chip by the innermost host span open at
        each gap's midpoint: ``label -> (ns, count)``."""
        a, b = self.window
        edges = [(a, a)] + self.chips[0].busy + [(b, b)]
        starts = [s[1] for s in self.spans]
        out: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0])
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 <= e0:
                continue
            mid = (e0 + s1) / 2
            label = "no span"
            # the latest-starting span that still covers the midpoint;
            # the benchmark's spans nest at most a few deep
            for j in range(bisect.bisect_right(starts, mid) - 1,
                           max(-1, bisect.bisect_right(starts, mid) - 9),
                           -1):
                name, s, d = self.spans[j]
                if mid < s + d:
                    label = name
                    break
            out[label][0] += s1 - e0
            out[label][1] += 1
        return {k: (v[0], int(v[1])) for k, v in out.items()}


def reduce_events(chips: Sequence[Tuple[Sequence[Event], Sequence[Event]]],
                  spans: Sequence[Event],
                  window: Optional[Tuple[int, int]] = None) -> Reduced:
    """``chips``: per chip ``(ops, modules)``. ``window`` defaults to the
    host span named ``WINDOW_SPAN``, else to the extent of the ops."""
    if window is None:
        marks = [s for s in spans if s[0] == WINDOW_SPAN]
        if marks:
            window = (marks[0][1], marks[0][1] + marks[0][2])
        else:
            ends = [(s, s + d) for ops, _ in chips for _, s, d in ops]
            window = (min(e[0] for e in ends), max(e[1] for e in ends))
    a, b = window
    out = []
    for ops, modules in chips:
        ops = [o for o in ops if o[1] < b and o[1] + o[2] > a]
        mods = sorted((m for m in modules if m[1] < b and m[1] + m[2] > a),
                      key=lambda m: m[1])
        starts = [m[1] for m in mods]
        own = self_times(ops)
        op_ns: Dict[str, int] = collections.Counter()
        for (name, s, d), o in zip(ops, own):
            j = bisect.bisect_right(starts, s) - 1
            mod = (module_name(mods[j][0])
                   if j >= 0 and s < mods[j][1] + mods[j][2] else "?")
            op_ns[f"{mod}/{op_name(name)}"] += o
        busy = merge((max(s, a), min(s + d, b)) for _, s, d in ops)
        out.append(Chip(busy=busy, op_ns=dict(op_ns), modules=mods))
    return Reduced(window=window, chips=out,
                   spans=sorted((s for s in spans
                                 if s[1] < b and s[1] + s[2] > a
                                 and s[0] != WINDOW_SPAN),
                                key=lambda s: s[1]))


def read_xplane(trace_dir: str, span_names: Iterable[str]) -> Reduced:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``: every TPU
    plane's "XLA Ops" and "XLA Modules" lines, and the host spans named
    in ``span_names`` (plus the window marker)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files in {trace_dir}")
    data = ProfileData.from_file(paths[0])
    wanted = set(span_names) | {WINDOW_SPAN}
    chips, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ev = {k: [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in lines[k].events] if k in lines else []
                  for k in ("XLA Ops", "XLA Modules")}
            chips.append((ev["XLA Ops"], ev["XLA Modules"]))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in ln.events if e.name in wanted]
    if not chips:
        raise ValueError(f"no TPU plane in {paths[0]}")
    return reduce_events(chips, spans)
