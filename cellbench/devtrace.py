"""Reduction of a JAX profiler trace to the device numbers of one window.

The traced run wraps its measured window in a ``cb.window`` annotation and
each unit of host work (a solver step, an append, a query) in a ``cb.*``
annotation of its own (``jax.profiler.TraceAnnotation``).  Host and device
events of one ``.xplane.pb`` share a clock, so the window bounds the device
events directly.

* busy: the union of the intervals of the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane inside the window, averaged over the chips;
* program time: the summed ``XLA Modules`` events of each jitted program
  inside the window, by program name (``jit_wavelet_forward``, ...);
* idle gaps: the stretches of the window in which no operation ran on a
  device, each named by the ``cb.*`` annotation that overlaps it most
  (``host`` where none does).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "cb.window"
_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                       # mean over the chips
    program_s: dict[str, float]         # jitted program name -> seconds
    gaps: list[tuple[str, float]]       # idle gaps, longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, kernel: str) -> float:
        """Device seconds of the jitted program that wraps ``kernel``."""
        return self.program_s.get(f"jit_{kernel}", 0.0)


def find_xplane(logdir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(pdata) -> DeviceTrace | None:
    """The window's device numbers from a ``jax.profiler.ProfileData``, or
    ``None`` where the trace holds no window or no device plane."""
    host = pdata.find_plane_with_name("/host:CPU")
    if host is None:
        return None
    spans, window = [], None
    for line in host.lines:
        for ev in line.events:
            if ev.name == WINDOW:
                window = (ev.start_ns, ev.start_ns + ev.duration_ns)
            elif ev.name.startswith("cb."):
                spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name))
    devices = [p for p in pdata.planes
               if re.fullmatch(r"/device:TPU:\d+", p.name)]
    if window is None or not devices or window[1] <= window[0]:
        return None
    lo, hi = window
    busy_total, program, union_all = 0.0, {}, []
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 lo, hi)
                    if e > s:
                        ops.append((s, e))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 lo, hi)
                    if e > s:
                        name = _HASH.sub("", ev.name)
                        program[name] = program.get(name, 0.0) + (e - s) / 1e9
        merged = _merge(ops)
        busy_total += sum(e - s for s, e in merged)
        union_all.extend(merged)
    busy = _merge(union_all)
    gaps, cursor = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > cursor:
            gaps.append((_name_gap(cursor, s, spans), (s - cursor) / 1e9))
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: -g[1])
    return DeviceTrace(window_s=(hi - lo) / 1e9,
                       busy_s=busy_total / len(devices) / 1e9,
                       program_s=program, gaps=gaps)


def _name_gap(s, e, spans) -> str:
    overlap = {}
    for a, b, name in spans:
        o = min(b, e) - max(a, s)
        if o > 0:
            overlap[name] = overlap.get(name, 0) + o
    return max(overlap, key=overlap.get) if overlap else "host"


def load(logdir: str) -> DeviceTrace | None:
    """Reduce the newest trace under ``logdir`` (``None`` if there is none)."""
    from jax.profiler import ProfileData

    path = find_xplane(logdir)
    return None if path is None else reduce(ProfileData.from_file(path))


def breakdown(dt: DeviceTrace, top: int = 10) -> dict:
    """The result line's ``breakdown``: the programs that took most device
    time, and the longest idle gaps by what the host was doing."""
    ops = sorted(dt.program_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in dt.gaps[:top]]}


def idle_percent(obs) -> float | None:
    """Percent of the traced window in which no operation ran on a chip."""
    return None if obs.device is None else 100.0 * obs.device.idle_share
