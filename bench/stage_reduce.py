"""Device time of each stage of the device body, from a profiler trace.

The program runs each stage of its device body under a ``jax.named_scope``
(``fct.stack``, ``fct.route``, ``fct.mr1``, ``fct.mr2``, ``fct.reduce``,
``fct.topk``, and ``fct.collective`` around every cross-device collective).
XLA copies the scope path into each op's ``op_name``, and the chip's trace
keeps it in the ``tf_op`` stat of the op's event metadata on a device
plane's ``XLA Ops`` line (``jit(fct_store)/fct.stack/concatenate:``).  The
stage of an op is the innermost ``fct.*`` scope of that name; a fused op
carries the name of the op at its root.  Ops without one (copies XLA
inserts with no metadata, or a program without the scopes) are
``unstaged``.

``jax.profiler.ProfileData`` gives an event's own stats but not its
metadata's, so ``read_trace`` reads the ``.xplane.pb`` file's protobuf wire
format itself, in one pass (``XSpace.planes`` -> ``XPlane.lines`` /
``event_metadata`` -> ``XStat``): each device op's name, times and
``tf_op`` come from the same record.

``reduce_stages`` sums the device time of every op that is not the
``fct_count`` kernel over the same window and with the same clipping as
``trace_reduce.reduce_trace``, so its stages add up to that reduction's
``xla_ops_s``.  It returns None when the trace holds no device op, and
``stages`` empty when no op carries a stage scope (a program without them).

    python bench/stage_reduce.py <trace dir>

prints the stage split of a trace kept by ``bench/run.py --keep-trace``
(the window from its ``window.json``).
"""
from __future__ import annotations

import glob
import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, Iterator, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

STAGES = ("stack", "route", "mr1", "mr2", "reduce", "topk", "collective")
UNSTAGED = "unstaged"
#: the event-metadata stat that holds the HLO ``op_name``
OP_NAME_STAT = "tf_op"
MODULES_LINE = "XLA Modules"
TOP = 10
_SCOPE = re.compile(r"\bfct\.(" + "|".join(STAGES) + r")\b")


def stage_of(op_name: Optional[str]) -> str:
    """The innermost ``fct.<stage>`` scope of an op name, or ``unstaged``."""
    scopes = _SCOPE.findall(op_name or "")
    return scopes[-1] if scopes else UNSTAGED


# -- the trace, from its file's wire format -----------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for varint and
    fixed-width fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value = int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _plane(buf) -> Optional[SimpleNamespace]:
    """A device plane's ``XLA Ops`` and ``XLA Modules`` lines, or the
    window-open marker's events on a host plane, in ``ProfileData``'s shape (plane
    ``name`` / ``lines``, line ``name`` / ``events``, event ``name`` /
    ``start_ns`` / ``duration_ns``), device ops also with ``op_name``."""
    name, lines, metadata, stat_names = "", [], {}, {}
    for field, value in _fields(buf):
        if field == 2:
            name = _text(value)
        elif field == 3:
            lines.append(value)
        elif field in (4, 5):       # map entry: key 1, value message 2
            entry = dict(_fields(value))
            if field == 4:
                metadata[entry.get(1, 0)] = entry.get(2, b"")
            else:
                stat = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(stat.get(2, b""))
    device = name.startswith("/device:") and "CPU" not in name
    if not (device or name.startswith("/host:")):
        return None
    op_stat = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
    names: Dict[int, Tuple[str, Optional[str]]] = {}
    for mid, md in metadata.items():
        ev_name, op_name = "", None
        for field, value in _fields(md):
            if field == 2:
                ev_name = _text(value)
            elif field == 5 and device:
                stat = dict(_fields(value))
                if stat.get(1) in op_stat:
                    # a string value, or a reference to an interned one
                    op_name = (_text(stat[5]) if 5 in stat
                               else stat_names.get(stat.get(7)))
        names[mid] = (ev_name, op_name)
    wanted = {mid for mid, (n, _) in names.items() if n == trace_reduce.MARKER}
    if not device and not wanted:
        return None
    out = []
    for line in lines:
        head, events = {}, []
        for field, value in _fields(line):
            if field == 4:
                events.append(value)
            else:
                head[field] = value
        line_name = _text(head.get(2, b""))
        if device and line_name not in (trace_reduce.OPS_LINE, MODULES_LINE):
            continue
        t0 = float(head.get(3, 0))
        evs = []
        for ev in events:
            ev = dict(_fields(ev))
            mid = ev.get(1, 0)
            if not device and mid not in wanted:
                continue
            ev_name, op_name = names.get(mid, ("", None))
            # whole nanoseconds, as ProfileData gives them
            evs.append(SimpleNamespace(
                name=ev_name, op_name=op_name,
                start_ns=t0 + ev.get(2, 0) // 1000,
                duration_ns=float(ev.get(3, 0) // 1000)))
        out.append(SimpleNamespace(name=line_name, events=evs))
    return SimpleNamespace(name=name, lines=out)


def read_trace(path) -> SimpleNamespace:
    """The planes of an ``.xplane.pb`` file that the reductions read."""
    space = memoryview(Path(path).read_bytes())
    planes = [_plane(value) for field, value in _fields(space)
              if field == 1]
    return SimpleNamespace(planes=[p for p in planes if p is not None])


# -- the reduction ------------------------------------------------------------

def reduce_stages(path, marker_ns: Optional[int], open_ns: int,
                  end_ns: int) -> Optional[dict]:
    """Seconds of non-kernel device time per stage over the window, summed
    over devices, and the ops that took most of it with their stage."""
    data = read_trace(path)
    offset = trace_reduce._marker_offset(data, marker_ns)
    per_plane = []
    for plane in trace_reduce._device_planes(data):
        ops = [ev for line in plane.lines
               if line.name == trace_reduce.OPS_LINE for ev in line.events]
        if ops:
            per_plane.append(ops)
    if not per_plane:
        return None
    if offset is None:
        lo = min(ev.start_ns for ops in per_plane for ev in ops)
        hi = max(ev.start_ns + ev.duration_ns
                 for ops in per_plane for ev in ops)
    else:
        lo, hi = float(open_ns + offset), float(end_ns + offset)
    ns = dict.fromkeys(STAGES + (UNSTAGED,), 0.0)
    by_op: Dict[tuple, float] = {}
    for ops in per_plane:
        for ev in ops:
            a = max(lo, ev.start_ns)
            b = min(hi, ev.start_ns + ev.duration_ns)
            if b <= a or trace_reduce.KERNEL in ev.name:
                continue
            stage = stage_of(ev.op_name)
            ns[stage] += b - a
            key = (ev.name.split("{", 1)[0].lstrip("%"), stage,
                   ev.op_name or "")
            by_op[key] = by_op.get(key, 0.0) + (b - a)
    staged = any(ns[s] for s in STAGES)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"stages": {k: v / 1e9 for k, v in ns.items()} if staged else {},
            "xla_ops_s": sum(ns.values()) / 1e9,
            "top_ops": [[op, stage, name, s / 1e9]
                        for (op, stage, name), s in top]}


def reduce_kept(trace_dir: Path) -> Optional[dict]:
    """``reduce_stages`` over a trace kept by ``bench/run.py --keep-trace``."""
    window = json.loads((trace_dir / "window.json").read_text())
    xplane, = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    return reduce_stages(xplane, window["marker_ns"], window["open_ns"],
                         window["end_ns"])


if __name__ == "__main__":
    print(json.dumps(reduce_kept(Path(sys.argv[1]))))
