"""Reduction of a JAX profiler trace of the window to per-layer numbers.

``Profile`` starts and stops the profiler around the window and drops a
marker annotation at the window's opening, which ties the program's host
spans (``time.perf_counter_ns``) to the trace's clock.  ``reduce_trace``
reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and returns, over
the window:

* ``fct_count_s``: device time of the ``fct_count`` kernel events (ops on a
  device plane's ``XLA Ops`` line whose name carries ``fct_count``);
* ``xla_ops_s``: device time of every other op on those lines;
* ``busy_s``: the union of all op intervals, averaged over the devices;
* ``window_s``: the window's length;
* ``breakdown``: the ops that took most device time, and the longest idle
  gaps, each labelled by the innermost host span open at its middle.
"""
from __future__ import annotations

import glob
import shutil
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

KERNEL = "fct_count"
OPS_LINE = "XLA Ops"
MARKER = "bench.window_open"
TOP = 10

Span = Tuple[str, int, int]     # (name, start_ns, end_ns) on perf_counter_ns


class Profile:
    def __init__(self, log_dir: Path) -> None:
        self.log_dir = Path(log_dir)
        self.marker_ns: Optional[int] = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        opts.enable_hlo_proto = False     # op names suffice; keeps it small
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)

    def mark_open(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(MARKER):
            self.marker_ns = time.perf_counter_ns()

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def reduce(self, t_open: float, t_end: float,
               spans: Sequence[Span] = ()) -> Optional[dict]:
        files = glob.glob(str(self.log_dir / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        if not files:
            return None
        from jax.profiler import ProfileData
        data = ProfileData.from_file(sorted(files)[-1])
        return reduce_trace(data, self.marker_ns, int(t_open * 1e9),
                            int(t_end * 1e9), spans)


def _device_planes(data) -> list:
    return [p for p in data.planes
            if p.name.startswith("/device:") and "CPU" not in p.name]


def _marker_offset(data, marker_ns: Optional[int]) -> Optional[int]:
    """trace clock minus perf_counter_ns, from the window-open marker."""
    if marker_ns is None:
        return None
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARKER:
                    return int(ev.start_ns) - marker_ns
    return None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def reduce_trace(data, marker_ns: Optional[int], open_ns: int, end_ns: int,
                 spans: Sequence[Span] = ()) -> Optional[dict]:
    """Numbers over ``[open_ns, end_ns]`` (perf_counter_ns), or None when
    the trace holds no device op."""
    offset = _marker_offset(data, marker_ns)
    planes = _device_planes(data)
    per_plane = []
    for plane in planes:
        ops = [ev for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        if ops:
            per_plane.append(ops)
    if not per_plane:
        return None
    if offset is None:         # no marker: the whole trace is the window
        lo = min(float(ev.start_ns) for ops in per_plane for ev in ops)
        hi = max(float(ev.start_ns + ev.duration_ns)
                 for ops in per_plane for ev in ops)
    else:
        lo, hi = float(open_ns + offset), float(end_ns + offset)
    kernel_ns = other_ns = busy_ns = 0.0
    by_name: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for ops in per_plane:
        iv = []
        for ev in ops:
            a = max(lo, float(ev.start_ns))
            b = min(hi, float(ev.start_ns + ev.duration_ns))
            if b <= a:
                continue
            iv.append((a, b))
            if KERNEL in ev.name:
                kernel_ns += b - a
            else:
                other_ns += b - a
            # "%fusion.3 = s32[8388608]{0:T(1024)} fusion(...)": the name
            # and the result shape
            name = ev.name.split("{", 1)[0].lstrip("%")
            by_name[name] = by_name.get(name, 0.0) + (b - a)
        busy = _union(iv)
        busy_ns += sum(b - a for a, b in busy)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n = len(per_plane)
    shift = offset if offset is not None else 0
    host = [(name, a + shift, b + shift) for name, a, b in spans]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label((a + b) / 2, host), (b - a) / 1e9] for a, b in gaps[:TOP]]
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {"fct_count_s": kernel_ns / 1e9, "xla_ops_s": other_ns / 1e9,
            "busy_s": busy_ns / n / 1e9, "window_s": (hi - lo) / 1e9,
            "devices": n, "kernel_events": sum(
                KERNEL in ev.name for ops in per_plane for ev in ops),
            "breakdown": {"device_ops": [[k, v / 1e9] for k, v in top_ops],
                          "idle_gaps": idle}}


def _label(t: float, host: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost (shortest) host span open at ``t``."""
    best = None
    for name, a, b in host:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "no request open"
