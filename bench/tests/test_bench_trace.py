"""The trace reduction on a short trace recorded on one TPU v5e chip
(an earlier form of ``sf1-star-repeat``, whose keywords were planted in
reserved ids of 8-token rows; seed 214, a 2 s window,
``bench/run.py --keep-trace``;
the HLO module protos of ``/host:metadata`` and the per-op metadata blobs,
which the reduction never reads, were stripped to keep the file small).
``window.json`` holds the run's marker, window, program spans and the
numbers the run reduced on the chip."""
import glob
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "repeat_trace"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    window = json.loads((DATA / "window.json").read_text())
    xplane, = glob.glob(str(DATA / "*.xplane.pb"))
    return ProfileData.from_file(xplane), window


def test_reduction_matches_the_chip_run(recorded):
    data, w = recorded
    got = trace_reduce.reduce_trace(data, w["marker_ns"], w["open_ns"],
                                    w["end_ns"], w["spans"])
    want = w["reduced"]
    for key in ("fct_count_s", "xla_ops_s", "busy_s", "window_s",
                "kernel_events"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got["breakdown"]["idle_gaps"] == want["breakdown"]["idle_gaps"]


def test_reduction_is_consistent(recorded):
    data, w = recorded
    got = trace_reduce.reduce_trace(data, w["marker_ns"], w["open_ns"],
                                    w["end_ns"], w["spans"])
    assert got["kernel_events"] > 0 and got["fct_count_s"] > 0
    assert got["devices"] == 1
    # the ops do not overlap on a core: their sum is their union
    assert got["busy_s"] <= got["window_s"]
    assert got["busy_s"] == pytest.approx(got["fct_count_s"]
                                          + got["xla_ops_s"], rel=1e-6)
    assert got["window_s"] == pytest.approx(
        (w["end_ns"] - w["open_ns"]) / 1e9, rel=1e-9)
    ops = got["breakdown"]["device_ops"]
    gaps = got["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and len(gaps) <= 10
    assert any("fct_count" in name for name, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert sum(s for _, s in gaps) <= got["window_s"] - got["busy_s"] + 1e-9


def test_no_marker_falls_back_to_the_whole_trace(recorded):
    data, w = recorded
    got = trace_reduce.reduce_trace(data, None, 0, 0)
    assert got["fct_count_s"] >= trace_reduce.reduce_trace(
        data, w["marker_ns"], w["open_ns"], w["end_ns"])["fct_count_s"]
    assert got["breakdown"]["idle_gaps"] == [] or all(
        label == "no request open" for label, _ in got["breakdown"][
            "idle_gaps"])
