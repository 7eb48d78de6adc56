"""Runtime: the jitted call of each dispatched group (``engine.enqueue``
spans: argument conversion and the host-to-device transfer it starts),
summed over the window's distinct spans per completed request.

Engine spans land only on a batch leader's trace, once per shared dispatch,
whereas ``dispatch_ms`` counts a shared dispatch once for every request in
it: where the batcher pairs two requests, this reads about half of the
share of ``dispatch_ms`` it covers."""

SPAN = "engine.enqueue"


def read(ctx):
    recs = ctx["records"]
    spans = {s for r in recs if r.response.trace is not None
             for s in r.response.trace.spans() if s.name == SPAN}
    if not spans:
        return None
    return sum(s.dur_ns for s in spans) / 1e6 / len(recs)
