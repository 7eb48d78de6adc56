"""Gateway layer: queue wait in the dynamic batcher (enqueue to flush), the
``batcher.window`` spans on each response's trace, per completed request."""


def read(ctx):
    spans = [s.dur_ns for r in ctx["records"] if r.response.trace is not None
             for s in r.response.trace.spans() if s.name == "batcher.window"]
    if not spans:
        return None
    return sum(spans) / 1e6 / len(ctx["records"])
