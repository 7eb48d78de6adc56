"""Device time of each stage of the device body with the snowflake hop
(``fct.hop``, nested inside ``fct.mr1``) split out, from a profiler trace.

The stages are ``bench/stage_reduce.py``'s, read with its trace reader and
window, plus ``hop``: an op whose innermost ``fct.*`` scope is
``fct.hop`` counts there, not under ``mr1``.  Ops the TPU compiler leaves
without an op name (MR¹'s and the hop's scatter-adds) stay ``unstaged``;
``tests/test_tpu_compile.py`` attributes them from the compiled program.

    python bench/hop_stages.py <trace dir>

prints the split of a trace kept by ``bench/run.py --keep-trace``.
"""
from __future__ import annotations

import glob
import json
import re
import sys
from pathlib import Path
from typing import Dict, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import stage_reduce, trace_reduce  # noqa: E402

STAGES = stage_reduce.STAGES + ("hop",)
_SCOPE = re.compile(r"\bfct\.(" + "|".join(STAGES) + r")\b")


def stage_of(op_name: Optional[str]) -> str:
    scopes = _SCOPE.findall(op_name or "")
    return scopes[-1] if scopes else stage_reduce.UNSTAGED


def reduce_hop_stages(path, marker_ns: Optional[int], open_ns: int,
                      end_ns: int) -> Optional[dict]:
    """Seconds of non-kernel device time per stage (``hop`` apart) over
    the window, summed over devices, clipped as ``stage_reduce`` clips."""
    data = stage_reduce.read_trace(path)
    offset = trace_reduce._marker_offset(data, marker_ns)
    ops = [ev for plane in trace_reduce._device_planes(data)
           for line in plane.lines if line.name == trace_reduce.OPS_LINE
           for ev in line.events]
    if not ops:
        return None
    if offset is None:
        lo = min(ev.start_ns for ev in ops)
        hi = max(ev.start_ns + ev.duration_ns for ev in ops)
    else:
        lo, hi = float(open_ns + offset), float(end_ns + offset)
    ns: Dict[str, float] = dict.fromkeys(STAGES + (stage_reduce.UNSTAGED,),
                                         0.0)
    for ev in ops:
        a = max(lo, ev.start_ns)
        b = min(hi, ev.start_ns + ev.duration_ns)
        if b > a and trace_reduce.KERNEL not in ev.name:
            ns[stage_of(ev.op_name)] += b - a
    return {"stages": {k: v / 1e9 for k, v in ns.items()},
            "xla_ops_s": sum(ns.values()) / 1e9}


def reduce_kept(trace_dir: Path) -> Optional[dict]:
    """``reduce_hop_stages`` over a trace kept by ``bench/run.py
    --keep-trace``."""
    window = json.loads((trace_dir / "window.json").read_text())
    xplane, = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    return reduce_hop_stages(xplane, window["marker_ns"], window["open_ns"],
                             window["end_ns"])


if __name__ == "__main__":
    print(json.dumps(reduce_kept(Path(sys.argv[1]))))
