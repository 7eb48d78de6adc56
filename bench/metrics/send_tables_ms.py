"""Runtime: the host pad-and-stack of the send tables and key-column indices
of each dispatched group (``store.send_tables`` spans), summed over the
window's distinct spans per completed request.

Engine and store spans land only on a batch leader's trace, once per shared
dispatch, whereas ``dispatch_ms`` counts a shared dispatch once for every
request in it: where the batcher pairs two requests, this reads about half
of the share of ``dispatch_ms`` it covers."""

SPAN = "store.send_tables"


def read(ctx):
    recs = ctx["records"]
    spans = {s for r in recs if r.response.trace is not None
             for s in r.response.trace.spans() if s.name == SPAN}
    if not spans:
        return None
    return sum(s.dur_ns for s in spans) / 1e6 / len(recs)
