"""Profiler trace of the measured window, and its reduction to numbers.

The window is traced with ``jax.profiler``; the harness marks it with a
``bench:window`` span and marks what the host does inside it (``bench:tick``,
``bench:draw`` around each block sampler call, ``bench:wait`` while no query
is due); the program adds its own ``isla:launch`` span.  ``reduce`` reads
the ``.xplane.pb`` file with ``jax.profiler.ProfileData`` and gives:

* busy seconds: the union of the device's operation intervals inside the
  window, averaged over the devices that ran anything;
* per-program device seconds (the "XLA Modules" line), for kernel times;
* the device operations that took most time;
* idle gaps: the device's idle time inside the window, each gap charged to
  the innermost host span that covers its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

WINDOW = "bench:window"
HOST_SPANS = ("bench:", "isla:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    modules: dict          # program name -> [seconds, calls]
    ops: dict              # op name -> seconds
    idle: dict             # host activity -> idle device seconds
    n_devices: int

    def module_seconds(self, needle: str) -> tuple:
        """(seconds, calls) of the device programs whose name holds
        ``needle``."""
        s = c = 0
        for name, (sec, calls) in self.modules.items():
            if needle in name:
                s += sec
                c += calls
        return s, c


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_planes(planes) -> Summary:
    """Reduce planes given as ``[(plane name, [(line name, [(event name,
    start_ns, duration_ns), ...]), ...]), ...]``."""
    host = []
    devices = []
    for pname, lines in planes:
        if pname.startswith("/device:"):
            ops = [ev for ln, evs in lines if ln == OPS_LINE for ev in evs]
            mods = [ev for ln, evs in lines if ln == MODULES_LINE
                    for ev in evs]
            if ops or mods:
                devices.append((ops or mods, mods))
        else:
            for _, evs in lines:
                host.extend((n, s, s + d) for n, s, d in evs
                            if n.startswith(HOST_SPANS))
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0, w1 = windows[0]
    busy_total = 0.0
    modules = defaultdict(lambda: [0.0, 0])
    ops_sec = defaultdict(float)
    idle = defaultdict(float)
    by_name = defaultdict(list)
    for n, s, e in host:
        if n != WINDOW:
            by_name[n].append((s, e))
    spans = {n: (sorted(v), [s for s, _ in sorted(v)])
             for n, v in by_name.items()}

    def host_activity(t):
        # The innermost span covering t: of each name's spans (which do not
        # overlap one another), the covering one that started last.
        best, best_start = "bench:window (no host span)", None
        for n, (ivs, starts) in spans.items():
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and ivs[i][1] > t and (best_start is None
                                             or ivs[i][0] > best_start):
                best, best_start = n, ivs[i][0]
        return best

    for ops, mods in devices:
        busy = _union(_clip([(s, s + d) for _, s, d in ops], w0, w1))
        busy_total += sum(e - s for s, e in busy)
        for name, s, d in ops:
            for cs, ce in _clip([(s, s + d)], w0, w1):
                ops_sec[name] += (ce - cs) / 1e9
        for name, s, d in mods:
            for cs, ce in _clip([(s, s + d)], w0, w1):
                modules[name][0] += (ce - cs) / 1e9
                modules[name][1] += 1
        prev = w0
        for s, e in busy + [[w1, w1]]:
            if s > prev:
                idle[host_activity((prev + s) / 2)] += (s - prev) / 1e9
            prev = max(prev, e)
    n_dev = max(len(devices), 1)
    return Summary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_total / 1e9 / n_dev,
        modules={k: [v[0] / n_dev, v[1]] for k, v in modules.items()},
        ops={k: v / n_dev for k, v in ops_sec.items()},
        idle={k: v / n_dev for k, v in idle.items()},
        n_devices=len(devices))


def read_planes(path: str) -> list:
    """The planes of an ``.xplane.pb`` file in ``reduce_planes``' form."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines.append((line.name, [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events
                if device or ev.name.startswith(HOST_SPANS)]))
        out.append((plane.name, lines))
    return out


def breakdown(summary: Summary, top: int = 10, name_chars: int = 200
              ) -> dict:
    """The ``breakdown`` entry of a traced run's result line (an op's
    name is its HLO text, cut to ``name_chars``)."""
    ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(summary.idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k[:name_chars], v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
