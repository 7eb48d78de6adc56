"""The ``pad_slots`` reader: null CN slots per completed request, from the
window's distinct ``engine.enqueue`` spans."""
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.metrics import pad_slots  # noqa: E402


class _Span:
    """A program span as a reader sees it (hashed by identity)."""

    def __init__(self, name, **args):
        self.name, self.args = name, args


def _records(*span_lists):
    return [SimpleNamespace(response=SimpleNamespace(
        trace=None if spans is None
        else SimpleNamespace(spans=lambda spans=spans: spans)))
        for spans in span_lists]


def test_pad_slots_reads_distinct_enqueue_spans_per_request():
    shared = [_Span("engine.enqueue", pad_slots=3, kind="fct_store_percn"),
              _Span("engine.enqueue", pad_slots=0, kind="fct_store_percn"),
              _Span("store.send_tables", n_stack=4)]
    # the batch leader's trace holds the shared dispatches; the other
    # request's trace holds none, a cache hit has no trace at all
    recs = _records(shared, [], shared, None)
    assert pad_slots.read({"records": recs}) == 3 / 4


def test_pad_slots_reads_zero_when_groups_are_exact():
    spans = [_Span("engine.enqueue", pad_slots=0, kind="fct_store_percn")]
    assert pad_slots.read({"records": _records(spans, [])}) == 0


def test_pad_slots_reads_nothing_from_a_program_without_the_counter():
    old = [_Span("engine.enqueue", hop_rows=100, kind="fct_store_percn")]
    assert pad_slots.read({"records": _records(old)}) is None
