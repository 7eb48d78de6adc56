"""The stage reduction and the dispatch readers on a short trace recorded on
one TPU v5e chip: ``sf1-star-repeat``, seed 2000001301, ``--seconds 2``
(one paired request window), ``bench/run.py --keep-trace``.  The trace was
cut to what the reductions read by ``data/stage_trace/strip_trace.py``:
the device planes' ``XLA Ops`` and ``XLA Modules`` lines, each op's name up
to its layout (whole where it names the kernel) and its ``tf_op`` stat,
and the host events named after program spans and modules.
``window.json`` holds the run's marker, window, program spans and reduced
numbers; ``line.json`` the metrics the run printed and the stage split of
the trace as first cut on the chip, which kept whole op names and event
stats (reduced on the CPU)."""
import glob
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import stage_reduce, trace_reduce  # noqa: E402
from bench.metrics import enqueue_ms, send_tables_ms  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "stage_trace"
HOST_SPANS = ("plan", "dispatch", "engine.dispatch_group", "store.send_tables",
              "engine.enqueue", "collect")
XPLANE, = glob.glob(str(DATA / "*.xplane.pb"))


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    window = json.loads((DATA / "window.json").read_text())
    line = json.loads((DATA / "line.json").read_text())
    return ProfileData.from_file(XPLANE), window, line


def _stages(w):
    return stage_reduce.reduce_stages(XPLANE, w["marker_ns"], w["open_ns"],
                                      w["end_ns"])


def test_stages_sum_to_the_non_kernel_device_time(recorded):
    data, w, _ = recorded
    got = _stages(w)
    ops = trace_reduce.reduce_trace(data, w["marker_ns"], w["open_ns"],
                                    w["end_ns"], w["spans"])
    assert set(got["stages"]) == set(stage_reduce.STAGES) | {
        stage_reduce.UNSTAGED}
    assert sum(got["stages"].values()) == pytest.approx(ops["xla_ops_s"],
                                                        rel=1e-6)
    assert ops["xla_ops_s"] == pytest.approx(w["reduced"]["xla_ops_s"],
                                             rel=1e-9)


def test_stage_reduction_matches_the_recorded_trace(recorded):
    _, w, line = recorded
    got = _stages(w)["stages"]
    for stage, seconds in line["stages"].items():
        assert got[stage] == pytest.approx(seconds, rel=1e-9, abs=1e-12), \
            stage


def _device_events(line_name):
    return [ev for plane in trace_reduce._device_planes(
                stage_reduce.read_trace(XPLANE))
            for line in plane.lines if line.name == line_name
            for ev in line.events]


def test_every_named_op_is_staged(recorded):
    """Only ops XLA made without an op name (the expanded scatter-adds,
    copies) or argument copies go unstaged; on one chip the collectives
    compile away and the per-CN family runs no cross-CN sum."""
    _, w, _ = recorded
    names = [ev.op_name for ev in _device_events(trace_reduce.OPS_LINE)]
    assert names
    for name in names:
        if name is not None and not name.startswith("args["):
            assert stage_reduce.stage_of(name) != stage_reduce.UNSTAGED, \
                name
    got = _stages(w)
    for stage in ("stack", "route", "mr1", "mr2"):
        assert got["stages"][stage] > 0, stage
    assert got["stages"]["collective"] == 0
    top = got["top_ops"]
    assert [t[1] for t in top[:4]] == ["route", "route", "mr1", "mr1"]
    assert all((stage == stage_reduce.UNSTAGED) == (name == "")
               for _, stage, name, _ in top)


def test_one_pass_read_agrees_with_profile_data(recorded):
    """``read_trace`` yields the device ops ``ProfileData`` yields, in the
    same order, with the same names and times."""
    data, _, _ = recorded
    theirs = [(ev.name, ev.start_ns, ev.duration_ns)
              for plane in trace_reduce._device_planes(data)
              for line in plane.lines if line.name == trace_reduce.OPS_LINE
              for ev in line.events]
    ours = [(ev.name, ev.start_ns, ev.duration_ns)
            for ev in _device_events(trace_reduce.OPS_LINE)]
    assert ours == theirs


def test_kernel_and_programs_are_named(recorded):
    kernel_stages = {stage_reduce.stage_of(ev.op_name)
                     for ev in _device_events(trace_reduce.OPS_LINE)
                     if trace_reduce.KERNEL in ev.name}
    modules = {ev.name.split("(", 1)[0]
               for ev in _device_events(stage_reduce.MODULES_LINE)}
    assert kernel_stages == {"mr2"}
    assert modules and all(m.startswith("jit_fct_") for m in modules), \
        modules


def test_program_spans_are_on_the_profiler_host_plane(recorded):
    data, _, _ = recorded
    names = {ev.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert set(HOST_SPANS) <= names
    assert not any(n.startswith("fct.dispatch_group") for n in names)


class _Span:
    def __init__(self, name, dur_ns):
        self.name, self.dur_ns = name, dur_ns


def _records(spans):
    """The run's completed requests, rebuilt from its flattened spans: each
    request's ``client.wait`` followed by the spans on its trace."""
    records = []
    for name, a, b in spans:
        if name == "client.wait":
            records.append([])
        else:
            records[-1].append(_Span(name, b - a))
    return [SimpleNamespace(response=SimpleNamespace(
        trace=SimpleNamespace(spans=lambda s=s: s))) for s in records]


@pytest.mark.parametrize("reader", [send_tables_ms, enqueue_ms],
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_dispatch_readers_match_the_chip_run(recorded, reader):
    _, w, line = recorded
    name = reader.__name__.rsplit(".", 1)[-1]
    got = reader.read({"records": _records(w["spans"])})
    assert got == pytest.approx(line["metrics"][name]["value"], rel=1e-9)


def test_dispatch_readers_cover_the_dispatch_group(recorded):
    _, w, _ = recorded
    recs = _records(w["spans"])
    group = sum(b - a for name, a, b in w["spans"]
                if name == "engine.dispatch_group") / 1e6 / len(recs)
    parts = send_tables_ms.read({"records": recs}) + enqueue_ms.read(
        {"records": recs})
    assert 0.8 * group <= parts <= group


def test_readers_report_nothing_without_the_spans():
    recs = [SimpleNamespace(response=SimpleNamespace(
        trace=SimpleNamespace(spans=lambda: [_Span("dispatch", 5)])))]
    assert send_tables_ms.read({"records": recs}) is None
    assert enqueue_ms.read({"records": recs}) is None


@pytest.mark.parametrize("op_name,stage", [
    ("jit(fct_store)/vmap(fct.route)/jit(_take)/gather", "route"),
    ("jit(fct_store_percn)/fct.reduce/fct.collective/psum", "collective"),
    ("jit(fct_store)/vmap(fct.mr2)/vmap(jit(fct_count_pallas_exact))",
     "mr2"),
    ("jit(fct_store)/fct.stack/concatenate", "stack"),
    ("jit(fct_store)/copy", "unstaged"),
    (None, "unstaged"),
])
def test_stage_of(op_name, stage):
    assert stage_reduce.stage_of(op_name) == stage
