"""The device's idle time in a traced window, split by the program span
that was open on the host while the chip idled.

While its tracer is on, the program opens a ``jax.profiler.TraceAnnotation``
for each of its spans (``repro.obs.trace``), marked with the stat
``repro_span``, so the spans lie on the ``/host:CPU`` plane of the same
``.xplane.pb`` as the device's operations.  Each stretch of the ``cb.window``
annotation in which no operation ran on any chip goes to the innermost
program span open at the time on any host thread (the one opened last), or
to ``host`` where none was open.

    python -m cellbench.hostspans <profile_dir> [--top N]

prints the idle seconds of each span, most first, then the longest idle
gaps, each named by the span that holds most of it.  A trace without
program spans (a program that does not annotate them) gives nothing.
"""
from __future__ import annotations

import argparse
import dataclasses
import heapq
import re
import sys
import warnings

from cellbench import devtrace

#: the stat ``repro.obs.trace`` sets on the annotations of its spans
MARK = "repro_span"
HOST = "host"


@dataclasses.dataclass
class Split:
    window_s: float
    idle_s: float
    by_span: dict[str, float]           # innermost span (or host) -> seconds
    gaps: list[tuple[str, float]]       # idle gaps, longest first
    spans: int                          # program spans in the window

    @property
    def unattributed_share(self) -> float:
        """Share of the idle time with no program span open."""
        return self.by_span.get(HOST, 0.0) / self.idle_s if self.idle_s else 0.0


def attribute(idle, spans):
    """Split idle time among the spans open during it.

    ``idle`` is sorted, disjoint ``(start, end)`` intervals; ``spans`` is
    ``(start, end, name)`` from any threads.  Returns ``({name: time},
    [{name: time} for each idle interval])``; time no span covers goes to
    ``host``.
    """
    spans = sorted(spans)
    points = sorted({p for iv in idle for p in iv}
                    | {p for s, e, _ in spans for p in (s, e)})
    total: dict[str, float] = {}
    per_gap = [{} for _ in idle]
    heap: list = []          # (-start, end, name): the newest open span on top
    nxt = gap = 0
    for t, t2 in zip(points, points[1:]):
        while nxt < len(spans) and spans[nxt][0] <= t:
            s, e, name = spans[nxt]
            heapq.heappush(heap, (-s, e, name))
            nxt += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        while gap < len(idle) and idle[gap][1] <= t:
            gap += 1
        if gap == len(idle) or idle[gap][0] > t:
            continue
        # a span that ended under the top is still in the heap; the top is
        # open, so it is the innermost
        name = heap[0][2] if heap else HOST
        total[name] = total.get(name, 0) + (t2 - t)
        per_gap[gap][name] = per_gap[gap].get(name, 0) + (t2 - t)
    return total, per_gap


def _marked(ev) -> bool:
    return any(name == MARK for name, _ in ev.stats)


def reduce(pdata) -> Split | None:
    """The window's idle split from a ``jax.profiler.ProfileData``, or
    ``None`` where the trace holds no window, no device plane or no
    program span."""
    host = pdata.find_plane_with_name("/host:CPU")
    if host is None:
        return None
    window, spans = None, []
    with warnings.catch_warnings():
        # the profiler's stats type warns on first use under Python 3.12
        warnings.simplefilter("ignore", DeprecationWarning)
        for line in host.lines:
            for ev in line.events:
                if ev.name == devtrace.WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif _marked(ev):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    devices = [p for p in pdata.planes
               if re.fullmatch(r"/device:TPU:\d+", p.name)]
    if window is None or not devices or window[1] <= window[0]:
        return None
    lo, hi = window
    spans = [(max(s, lo), min(e, hi), n) for s, e, n in spans
             if min(e, hi) > max(s, lo)]
    if not spans:
        return None
    ops = []
    for plane in devices:
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    s, e = devtrace._clip(ev.start_ns,
                                          ev.start_ns + ev.duration_ns, lo, hi)
                    if e > s:
                        ops.append((s, e))
    idle, cursor = [], lo
    for s, e in devtrace._merge(ops) + [[hi, hi]]:
        if s > cursor:
            idle.append((cursor, s))
        cursor = max(cursor, e)
    total, per_gap = attribute(idle, spans)
    gaps = sorted(((max(g, key=g.get), (e - s) / 1e9)
                   for g, (s, e) in zip(per_gap, idle) if g),
                  key=lambda g: -g[1])
    return Split(window_s=(hi - lo) / 1e9,
                 idle_s=sum(e - s for s, e in idle) / 1e9,
                 by_span={k: v / 1e9 for k, v in total.items()},
                 gaps=gaps, spans=len(spans))


def load(profile_dir: str) -> Split | None:
    """Reduce the newest trace under ``profile_dir`` (``None`` if none)."""
    from jax.profiler import ProfileData

    path = devtrace.find_xplane(profile_dir)
    return None if path is None else reduce(ProfileData.from_file(path))


def profile_dir(obs) -> str | None:
    """The traced run's profile directory: the observation's own where it
    carries one, else that of the run's ``harness.Context``, which the
    harness holds on the stack beside the observation it hands a reader."""
    from cellbench import harness

    found = getattr(obs, "profile_dir", None)
    frame = sys._getframe(1)
    while found is None and frame is not None:
        found = next((v.profile_dir for v in frame.f_locals.values()
                      if isinstance(v, harness.Context)), None)
        frame = frame.f_back
    return found


def unattributed_percent(obs) -> float | None:
    """Percent of the device's idle time in which no program span was
    open on any host thread."""
    if obs.device is None:
        return None
    where = profile_dir(obs)
    split = None if where is None else load(where)
    return None if split is None else 100.0 * split.unattributed_share


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m cellbench.hostspans",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("profile_dir")
    ap.add_argument("--top", type=int, default=10,
                    help="idle gaps to list (default 10)")
    args = ap.parse_args(argv)
    split = load(args.profile_dir)
    if split is None:
        print(f"no window, device plane or program span under "
              f"{args.profile_dir}", file=sys.stderr)
        return 1
    print(f"window {split.window_s!r} s, device idle {split.idle_s!r} s, "
          f"{split.spans} program spans")
    print("idle by innermost open span:")
    for name, sec in sorted(split.by_span.items(), key=lambda kv: -kv[1]):
        print(f"  {name:24s} {sec!r} s  {100.0 * sec / split.idle_s:.2f}%")
    print(f"longest idle gaps ({args.top}):")
    for name, sec in split.gaps[:args.top]:
        print(f"  {name:24s} {sec!r} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
