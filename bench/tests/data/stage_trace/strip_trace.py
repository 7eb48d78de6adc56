"""Cut a profiler trace kept by ``bench/run.py --keep-trace`` down to what
the stage reduction and its tests read; this is how ``repeat.xplane.pb``
beside it was made from the chip's trace.

Keeps the device planes' ``XLA Ops`` and ``XLA Modules`` lines, their
events' times without their stats, each op's name up to its layout (whole
where it names the ``fct_count`` kernel) and its ``tf_op`` stat; on host
planes only the events named after program
spans, programs or the window marker.  Other planes and lines are dropped.
Needs TensorFlow's ``xplane_pb2``.

    python bench/tests/data/stage_trace/strip_trace.py IN.xplane.pb OUT.xplane.pb
"""
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

HOST_KEEP = ("plan", "dispatch", "engine.dispatch_group", "store.send_tables",
             "engine.enqueue", "collect", "engine.topk_finalize",
             "store.upload", "cache.lookup", "bench.window_open")
DEVICE_LINES = ("XLA Ops", "XLA Modules")
KERNEL = "fct_count"


def keep_host_event(name: str) -> bool:
    return name in HOST_KEEP or name.startswith("PjitFunction(")


def op_label(name: str) -> str:
    return name if KERNEL in name else name.split("{", 1)[0]


def strip(src: "xplane_pb2.XSpace") -> "xplane_pb2.XSpace":
    out = xplane_pb2.XSpace()
    for p in src.planes:
        dev = p.name.startswith("/device:") and "CPU" not in p.name
        if not (dev or p.name.startswith("/host:CPU")):
            continue
        q = out.planes.add()
        q.id, q.name = p.id, p.name
        for k, v in p.stat_metadata.items():
            q.stat_metadata[k].CopyFrom(v)
        if dev:
            q.stats.extend(p.stats)
        tf_op = {k for k, v in p.stat_metadata.items() if v.name == "tf_op"}
        used = set()
        for ln in p.lines:
            if dev and ln.name not in DEVICE_LINES:
                continue
            evs = [e for e in ln.events if dev or keep_host_event(
                p.event_metadata[e.metadata_id].name)]
            if not evs:
                continue
            nl = q.lines.add()
            nl.CopyFrom(ln)
            del nl.events[:]
            nl.events.extend(evs)
            if dev:
                for e in nl.events:
                    del e.stats[:]
            used.update(e.metadata_id for e in evs)
        for k in used:
            md, nm = p.event_metadata[k], q.event_metadata[k]
            nm.id = md.id
            if dev:
                nm.name = op_label(md.name)
            else:
                nm.name, nm.display_name = md.name, md.display_name
            nm.stats.extend(s for s in md.stats if s.metadata_id in tf_op)
    return out


if __name__ == "__main__":
    space = xplane_pb2.XSpace()
    with open(sys.argv[1], "rb") as f:
        space.ParseFromString(f.read())
    with open(sys.argv[2], "wb") as f:
        f.write(strip(space).SerializeToString())
