"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: on each chip the union of its operations' intervals
(busy), the time in which only collectives run (exposed), the operations that
took most time, and the longest idle gaps tagged by the host span they fell
in. The host spans are the benchmark's own ``TraceAnnotation``s, named
``chipbench.*``.

On a TPU the "XLA Ops" line nests: a ``while`` loop's event encloses the
events of its body. Busy time is the union of all of them; an operation's
own time leaves out what its nested operations take; collectives and
compute are told apart on the innermost operations only.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "chipbench."
COLLECTIVE = re.compile(
    r"^(all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute)", re.I)
HLO = re.compile(r"^%?([^\s=]+)(?: = (\w+\[[\d,]*\]))?")


def short(name: str) -> str:
    """An HLO op's name and, where it is an array, its type:
    "%convert.76 = bf16[28,2048]{1,0} convert(...)" -> "convert.76 bf16[28,2048]"."""
    m = HLO.match(name)
    return " ".join(g for g in m.groups() if g) if m else name


def nest(ops):
    """(short name, start, end, own time, innermost?) of each op of a line,
    with own time the part that no op nested in it covers."""
    out, stack = [], []
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [short(n), s, e, e - s, True]
        if stack and e <= stack[-1][2]:
            stack[-1][3] -= e - s
            stack[-1][4] = False
        out.append(rec)
        stack.append(rec)
    return out


def merge(intervals):
    """Sorted, non-overlapping union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class Chip:
    busy_ns: float
    collective_exposed_ns: float
    ops_ns: dict = field(default_factory=dict)
    idle: list = field(default_factory=list)  # (start, end) within the window


@dataclass
class Summary:
    window_ns: float
    chips: dict  # device id -> Chip
    gaps: list  # [(tag, seconds)] longest idle gaps of chip 0 first

    @property
    def busy_s(self) -> float:
        return sum(c.busy_ns for c in self.chips.values()) / len(self.chips) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def collective_exposed_share(self) -> float:
        return (sum(c.collective_exposed_ns for c in self.chips.values())
                / len(self.chips) / self.window_ns)

    def top_ops(self, k: int = 10):
        """[[name, seconds]] of the ops with most own time, as the mean over
        chips."""
        tot = {}
        for c in self.chips.values():
            for n, v in c.ops_ns.items():
                tot[n] = tot.get(n, 0.0) + v
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / len(self.chips) / 1e9] for n, v in top]


def events(profile):
    """(plane, line, name, start_ns, end_ns) of every event of a ProfileData."""
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                yield plane.name, line.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def summarize(evs, window: str = "chipbench.window", n_gaps: int = 10) -> Summary:
    """Reduce trace events to a Summary over the host span named ``window``."""
    evs = list(evs)
    spans = [(n, s, e) for p, _, n, s, e in evs
             if not DEVICE_PLANE.match(p) and n.startswith(HOST_PREFIX)]
    wins = [(s, e) for n, s, e in spans if n == window]
    if not wins:
        raise ValueError(f"no host span {window!r} in the trace")
    lo, hi = wins[0]
    ops = {}
    for p, line, n, s, e in evs:
        m = DEVICE_PLANE.match(p)
        if m and line == OPS_LINE and e > lo and s < hi:
            ops.setdefault(int(m.group(1)), []).append((n, max(s, lo), min(e, hi)))
    if not ops:
        raise ValueError("no device operation in the traced window")
    chips = {}
    for dev, lst in sorted(ops.items()):
        busy = merge([(s, e) for _, s, e in lst])
        inner = [o for o in nest(lst) if o[4]]
        coll = merge([(s, e) for n, s, e, _, _ in inner if COLLECTIVE.match(n)])
        comp = merge([(s, e) for n, s, e, _, _ in inner if not COLLECTIVE.match(n)])
        per = {}
        for n, _, _, own, _ in nest(lst):
            per[n] = per.get(n, 0.0) + own
        chips[dev] = Chip(length(busy), length(subtract(coll, comp)), per,
                          subtract([(lo, hi)], busy))
    inside = [(n, s, e) for n, s, e in spans if n != window and e > lo and s < hi]
    first = chips[min(chips)]
    gaps = []
    for s, e in sorted(first.idle, key=lambda g: g[0] - g[1])[:n_gaps]:
        mid = (s + e) / 2
        # the innermost (shortest) host span that holds the gap's middle
        holders = sorted((ee - ss, n) for n, ss, ee in inside if ss <= mid < ee)
        gaps.append([holders[0][1] if holders else "outside any span", (e - s) / 1e9])
    return Summary(hi - lo, chips, gaps)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
