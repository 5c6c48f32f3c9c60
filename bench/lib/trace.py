"""From a profiler trace and the program's host spans to per-layer numbers.

Two stages.  ``load_xplane`` reads the ``.xplane.pb`` that
``jax.profiler`` wrote and keeps what the reduction needs, as plain lists
of ``[name, start_ns, end_ns]`` on the trace's clock:

  ops          device operations, from the "XLA Ops" line of every
               ``/device:TPU:<n>`` plane, per chip
  modules      compiled programs run on the device ("XLA Modules"), per chip
  rounds       the harness's ``bench.round`` annotations on the host

``Reduction`` works on those lists (and on the program's ``repro.obs``
spans, shifted onto the trace's clock), so it can be checked on a small
excerpt committed with the tests:

  busy_ns      the union of the device operations' intervals inside the
               traced window, averaged over the chips used
  gaps         the device's idle intervals in the window
  idle_by_span the idle time, split by the innermost host span that was
               open at each instant ("(no span)" where none was)
  top_ops      device self time by operation name
"""
from __future__ import annotations

import glob
import gzip
import json
from collections import defaultdict
from pathlib import Path

ROUND_ANNOTATION = "bench.round"
DEVICE_PLANE = "/device:TPU:"


def load_xplane(trace_dir: str | Path) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, modules, rounds = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            chip = plane.name[len(DEVICE_PLANE):]
            if not chip.isdigit():
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [[op_name(e), e.start_ns, e.end_ns]
                                 for e in line.events]
                elif line.name == "XLA Modules":
                    modules[chip] = [[e.name, e.start_ns, e.end_ns]
                                     for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                rounds += [[e.name, e.start_ns, e.end_ns]
                           for e in line.events
                           if e.name == ROUND_ANNOTATION]
    return {"ops": ops, "modules": modules, "rounds": sorted(rounds,
                                                             key=lambda e:
                                                             e[1])}


def op_name(event) -> str:
    """An operation's HLO name (``fusion.12``, ``custom-call.3``): the
    event's ``hlo_op`` stat, else its text up to `` = ``."""
    name = dict(event.stats).get("hlo_op") or event.name.split(" = ")[0]
    return str(name).lstrip("%")


def self_ns(events) -> dict[str, float]:
    """Exclusive time by name of events on one line: an event that
    contains others (a loop around its body) keeps only the time none of
    them covers."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []      # [name, start, end, covered by children]

    def close(ev):
        out[ev[0]] += (ev[2] - ev[1]) - ev[3]

    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([n, s, e, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``[start, end]`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals
                       if e > lo and s < hi):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The sub-intervals of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals
                       if e > lo and s < hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def innermost_split(gap: tuple[float, float], spans) -> dict[str, float]:
    """Split ``gap`` by the innermost (shortest) span open at each instant."""
    lo, hi = gap
    inside = [(s, e, n) for n, s, e in spans if e > lo and s < hi]
    cuts = sorted({lo, hi} | {max(lo, min(hi, t)) for s, e, _ in inside
                              for t in (s, e)})
    out: dict[str, float] = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        if b <= a:
            continue
        mid = (a + b) / 2
        open_ = [(e - s, n) for s, e, n in inside if s <= mid < e]
        out[min(open_)[1] if open_ else "(no span)"] += b - a
    return out


class Reduction:
    """The traced window's numbers.  ``events`` is ``load_xplane``'s
    result (or a committed excerpt of it); ``spans`` are ``[name,
    start_ns, end_ns]`` host spans already on the trace's clock."""

    def __init__(self, events: dict, spans=()):
        rounds = events["rounds"]
        if not rounds:
            raise ValueError("the trace holds no bench.round annotation")
        self.lo = min(r[1] for r in rounds)
        self.hi = max(r[2] for r in rounds)
        self.n_rounds = len(rounds)
        self.chips = sorted(events["ops"], key=int)
        if not self.chips:
            raise ValueError("the trace holds no device operations")
        self.ops = {c: [(n, s, e) for n, s, e in events["ops"][c]
                        if e > self.lo and s < self.hi] for c in self.chips}
        self.modules = {c: [(n, s, e) for n, s, e in
                            events["modules"].get(c, [])
                            if e > self.lo and s < self.hi]
                        for c in self.chips}
        self.spans = [(n, s, e) for n, s, e in spans
                      if e > self.lo and s < self.hi]

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def busy_ns(self) -> float:
        return sum(union_ns([(s, e) for _, s, e in self.ops[c]], self.lo,
                            self.hi) for c in self.chips) / len(self.chips)

    def gaps(self) -> list[tuple[float, float]]:
        """Idle intervals of the first chip."""
        return idle_gaps([(s, e) for _, s, e in self.ops[self.chips[0]]],
                         self.lo, self.hi)

    def idle_by_span(self) -> dict[str, float]:
        out: dict[str, float] = defaultdict(float)
        for g in self.gaps():
            for name, ns in innermost_split(g, self.spans).items():
                out[name] += ns
        return dict(out)

    def top_ops(self) -> dict[str, float]:
        """Device self time (ns) by operation name (first chip), clipped
        to the window."""
        return self_ns([(n, max(s, self.lo), min(e, self.hi))
                        for n, s, e in self.ops[self.chips[0]]])

    def module_ns(self, prefix: str) -> float:
        """Device ns of the compiled programs whose name starts with
        ``prefix`` (first chip)."""
        return sum(min(e, self.hi) - max(s, self.lo)
                   for n, s, e in self.modules[self.chips[0]]
                   if n.startswith(prefix))

    def op_ns(self, match) -> tuple[float, int]:
        """(device ns, event count) of the operations ``match(name)``
        accepts (first chip)."""
        evs = [(s, e) for n, s, e in self.ops[self.chips[0]] if match(n)]
        return (sum(min(e, self.hi) - max(s, self.lo) for s, e in evs),
                len(evs))

    def span_union_ns(self, names) -> float:
        return union_ns([(s, e) for n, s, e in self.spans if n in names],
                        self.lo, self.hi)

    def span_ns(self, name: str) -> float:
        return sum(min(e, self.hi) - max(s, self.lo)
                   for n, s, e in self.spans if n == name)


def top(d: dict[str, float], n: int = 10) -> list[list]:
    """The ``n`` largest entries as ``[name, seconds]``."""
    return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
            [:n]]


def save_excerpt(events: dict, spans, path: Path, rounds: int = 1) -> None:
    """The first ``rounds`` traced rounds of ``events`` and ``spans``,
    gzipped JSON, for the reduction's tests."""
    r = events["rounds"][:rounds]
    lo, hi = r[0][1], r[-1][2]

    def clip(evs):
        return [list(e) for e in evs if e[2] > lo and e[1] < hi]

    with gzip.open(path, "wt") as f:
        json.dump({"ops": {c: clip(v) for c, v in events["ops"].items()},
                   "modules": {c: clip(v)
                               for c, v in events["modules"].items()},
                   "rounds": r, "spans": clip(spans)}, f)
