"""Reduction of a JAX profiler trace to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData`` (nothing but JAX).  The device's
operations are the events of the ``XLA Ops`` line of each
``/device:<kind>:<i>`` plane, named by their HLO instruction text; the
traced window is the host ``TraceAnnotation`` the harness opens around
the measured queries.  From those, :func:`reduce` computes

* ``busy_s``: the union of the operation intervals inside the window,
  averaged over the devices;
* per-operation device time inside the window (``op_seconds``, by HLO
  text; the breakdown
  names each by the instruction's name, kind and result type), and the
  time of the operations whose HLO text matches a kernel
  (``kernel_seconds``);
* the idle gaps between operations, each attributed to the host span
  (``repro.obs.trace`` recorder spans, moved onto the profiler's clock)
  that covers most of it.
"""
from __future__ import annotations

import re
from bisect import bisect_left
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_KIND = re.compile(r"\s([a-z][a-z0-9-]*)\(")
# recorder spans that cover whole waves, phases or queries: a gap is laid
# to them only when no narrower span covers it
UMBRELLA = ("wave", "query")
UMBRELLA_PREFIX = ("phase:",)
NO_SPAN = "host outside recorder spans"
# gaps this short lie between the operations of one program on the
# device; no host span is their cause
SHORT_GAP_NS = 1000.0
SHORT_GAP = "device: gaps under 1 us inside a program"


def short_name(hlo: str) -> str:
    """``%fusion.31 = s32[10649600]{0:T(1024)} fusion(...), ...`` ->
    ``%fusion.31 fusion s32[10649600]``."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    m = _KIND.search(rhs)
    kind = m.group(1) if m else "?"
    typ = "tuple" if rhs.startswith("(") else rhs.split("{", 1)[0].split(
        " ", 1)[0]
    return f"{lhs} {kind} {typ}"


@dataclass
class Reduction:
    window_s: float
    busy_s: float                      # averaged over devices
    n_devices: int
    op_seconds: dict[str, float] = field(repr=False)   # HLO text -> s
    # (seconds over the number of devices, cause)
    gaps: list[tuple[float, str]] = field(repr=False)

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, match) -> float:
        """Device time of the operations whose HLO text ``match(text)``
        accepts, averaged over the devices (so that it compares with
        ``window_s``)."""
        return sum(s for k, s in self.op_seconds.items()
                   if match(k)) / self.n_devices

    def gap_seconds(self) -> dict[str, float]:
        out: dict[str, float] = defaultdict(float)
        for s, cause in self.gaps:
            out[cause] += s
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        short: dict[str, float] = defaultdict(float)
        for k, s in self.op_seconds.items():
            short[short_name(k)] += s
        ops = sorted(short.items(), key=lambda kv: -kv[1])
        gaps = sorted(self.gap_seconds().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v / self.n_devices]
                               for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def host_annotation(profile, name: str) -> tuple[float, float]:
    """(start_ns, end_ns) of the one host event called ``name``."""
    found = [(ev.start_ns, ev.end_ns)
             for plane in profile.planes if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events
             if ev.name == name]
    if len(found) != 1:
        raise ValueError(f"expected one host event {name!r} in the trace, "
                         f"found {len(found)}")
    return found[0]


def device_lines(profile) -> dict[int, object]:
    """The ``XLA Ops`` line of each device plane (``/device:TPU:0`` -> 0)."""
    out = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[int(m.group(1))] = line
    return out


def union(intervals: list[tuple[float, float]], lo: float, hi: float
          ) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    if not intervals:
        return []
    a = np.clip(np.array([i[0] for i in intervals], np.float64), lo, hi)
    b = np.clip(np.array([i[1] for i in intervals], np.float64), lo, hi)
    keep = b > a
    a, b = a[keep], b[keep]
    if a.size == 0:
        return []
    order = np.argsort(a, kind="stable")
    a, b = a[order], np.maximum.accumulate(b[order])
    new = np.concatenate([[True], a[1:] > b[:-1]])
    starts = a[new]
    ends = b[np.concatenate([new[1:], [True]])]
    return list(zip(starts.tolist(), ends.tolist()))


class _Spans:
    """Spans sorted by start, for the overlap search of one gap."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.maxlen = max((b - a for _, a, b in self.spans), default=0.0)

    def best(self, g0: float, g1: float) -> tuple[str, float]:
        """The span with the largest overlap with [g0, g1], and it."""
        best = ("", 0.0)
        i = bisect_left(self.starts, g0 - self.maxlen)
        j = bisect_left(self.starts, g1)
        for name, a, b in self.spans[i:j]:
            ov = min(b, g1) - max(a, g0)
            if ov > best[1]:
                best = (name, ov)
        return best


def _is_umbrella(name: str) -> bool:
    return name in UMBRELLA or name.startswith(UMBRELLA_PREFIX)


def attributor(spans):
    """``cause(g0, g1)``: the name of the span that covers most of the gap;
    umbrella spans only where no other span touches it."""
    narrow = _Spans([s for s in spans if not _is_umbrella(s[0])])
    wide = _Spans([s for s in spans if _is_umbrella(s[0])])

    def cause(g0: float, g1: float) -> str:
        if g1 - g0 < SHORT_GAP_NS:
            return SHORT_GAP
        return narrow.best(g0, g1)[0] or wide.best(g0, g1)[0] or NO_SPAN

    return cause


def reduce(profile, window: str,
           spans: list[tuple[str, float, float]] = ()) -> Reduction:
    """Reduce ``profile`` over the host annotation ``window``.  ``spans``
    are (name, start_ns, end_ns) on the profiler's clock."""
    lo, hi = host_annotation(profile, window)
    lines = device_lines(profile)
    if not lines:
        raise ValueError("no device operations in the trace")
    n = len(lines)
    cause = attributor(spans)
    busy, gaps = 0.0, []
    op_seconds: dict[str, float] = defaultdict(float)
    for _, line in sorted(lines.items()):
        intervals = []
        for ev in line.events:
            a, b = ev.start_ns, ev.end_ns
            if b <= lo or a >= hi:
                continue
            intervals.append((a, b))
            op_seconds[ev.name] += (min(b, hi) - max(a, lo)) / 1e9
        merged = union(intervals, lo, hi)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append(((g1 - g0) / 1e9 / n, cause(g0, g1)))
    return Reduction(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9 / n,
                     n_devices=n, op_seconds=dict(op_seconds), gaps=gaps)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
