"""One run of one cell: set-up, the measured window, the comparison, and
the reduction of spans, counters and the device trace to metrics.

A traffic driver (``drivers/<name>.py``) has one function,
``run(ctx) -> Outcome``.  It sets up through the program's entry points,
wraps its measured work in
``with ctx.window():``, and compares what the window produced with the
reference once the window has closed.  The window records the set-up time,
the memory peak and, in a traced run, the program's spans and the profiler
trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import time

from . import check, devtrace, registry

PROFILE_DIR = "profile"


class _CompileCounter:
    """Backend compiles (and persistent-cache loads) of this process."""

    _instance = None

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    @classmethod
    def get(cls) -> "_CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


@dataclasses.dataclass
class Outcome:
    """What a driver hands back: end-to-end values by metric name, the
    requests attempted and failed, the readings of the comparison, and the
    counters the per-layer readers take."""

    metrics: dict
    attempted: int
    failed: int
    readings: check.Readings
    counters: dict


@dataclasses.dataclass
class Observation:
    """What a per-layer reader reads."""

    spans: list           # the program's trace events of the window
    counters: dict        # the traffic driver's counters over the window
    device: devtrace.DeviceTrace | None
    peaks: dict | None
    config: dict
    traffic: dict

    def span_seconds(self, name: str) -> list[float]:
        return [ev["dur"] / 1e6 for ev in self.spans
                if ev.get("name") == name and ev.get("ph") == "X"]


class Context:
    """A traffic driver's view of its run."""

    def __init__(self, workload: str, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, t0: float, workdir: str,
                 control: bool = False):
        self.workload, self.config, self.traffic = workload, config, traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.t0, self.workdir, self.control = t0, workdir, control
        self.setup_s = self.window_s = None
        self.start = self.deadline = None
        self.spans: list = []
        self.memory_peak = 0
        self.window_compiles = 0
        self.profile_dir = os.path.join(workdir, PROFILE_DIR)

    @staticmethod
    def annotate(name: str):
        """A host span in the profiler's trace (no cost when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    def mark(self, phase: str) -> None:
        """Note on standard error how far set-up has come."""
        print(f"setup {phase} {time.perf_counter() - self.t0:.3f} s",
              file=sys.stderr)

    def expired(self) -> bool:
        return time.perf_counter() >= self.deadline

    @contextlib.contextmanager
    def window(self):
        """The measured window.  The traffic driver finishes the work it started
        and waits for the device before leaving it."""
        import jax
        from repro.obs import trace as ptrace

        compiles = _CompileCounter.get()
        self.mark("window")
        self.setup_s = time.perf_counter() - self.t0
        if self.trace:
            ptrace.TRACER.reset()
            ptrace.enable()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.profile_dir, profiler_options=opts)
        c0 = compiles.count
        with self.annotate(devtrace.WINDOW):
            self.start = time.perf_counter()
            self.deadline = self.start + self.seconds
            yield self
            self.window_s = time.perf_counter() - self.start
        self.window_compiles = compiles.count - c0
        if self.trace:
            jax.profiler.stop_trace()
            ptrace.disable()
            self.spans = ptrace.TRACER.events()
        self.memory_peak = peak_bytes()


def peak_bytes() -> int:
    """Peak device memory of the fullest local chip (0 where the backend
    keeps no statistics).

    The TPU runtime counts arrays (``peak_bytes_in_use``) apart from what
    it reserves for compiled programs' temporaries (``peak_bytes_reserved``:
    6.4 GB for the Euler step at 256^3, against 1.35 GB of arrays), so the
    peak is their sum."""
    import jax

    def peak(stats: dict) -> int:
        return (stats.get("peak_bytes_in_use", 0)
                + stats.get("peak_bytes_reserved", 0))

    return int(max((peak(d.memory_stats() or {}) for d in jax.local_devices()),
                   default=0))


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def execute(workload: str, seed: int, seconds: float, trace: bool, t0: float,
            workdir: str, bench: dict | None = None,
            config_override: dict | None = None,
            traffic_override: dict | None = None, control: bool = False) -> dict:
    """Run one cell once; returns the result line as a dict.

    ``config_override`` and ``traffic_override`` replace keys of the
    configuration and the mix (tests run the drivers at small sizes through
    them); ``control`` puts the reference,
    rounded to the precision below the configuration's, in the program's
    place for the comparison.
    """
    bench = bench or registry.benchmark()
    cell = registry.cell(bench, workload)
    config = {**registry.config(cell["config"]), **(config_override or {})}
    mix = {**registry.traffic(cell["traffic"]), **(traffic_override or {})}
    limits = registry.limits(workload)
    ctx = Context(workload, config, mix, seed, seconds, trace, t0, workdir,
                  control=control)
    out = registry.driver(mix).run(ctx)

    info = device_info()
    checks = check.judge(out.readings.values(), limits)
    result = {"correct": check.correct(checks) and out.failed == 0,
              "attempted": out.attempted, "failed": out.failed}
    device = {**info, "memory_peak_bytes": ctx.memory_peak}
    if not trace:
        metrics = {"setup_s": ctx.setup_s, **out.metrics}
        result["metrics"] = {
            m["name"]: {"value": metrics[m["name"]], "unit": m["unit"]}
            for m in registry.end_to_end(bench, cell)}
    else:
        dt = devtrace.load(ctx.profile_dir)
        if dt is not None:
            device.update(busy_s=dt.busy_s, window_s=dt.window_s)
            result["breakdown"] = devtrace.breakdown(dt)
        peaks = (registry.peaks(info["kind"]) if info["platform"] == "tpu"
                 else None)
        obs = Observation(ctx.spans, out.counters, dt, peaks, config, mix)
        result["metrics"] = {}
        for m in registry.per_layer(bench, cell):
            value = registry.reader(m["name"])(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    result["device"] = device
    result["window"] = {"seconds": ctx.window_s,
                        "compiles": ctx.window_compiles}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result
