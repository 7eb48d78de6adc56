"""Runtime: ``timings["dispatch_ms"]`` (store lookups or uploads, send-table
stacking, the async enqueue), summed over the window per completed
request."""


def read(ctx):
    recs = ctx["records"]
    if not recs:
        return None
    return sum(r.response.timings["dispatch_ms"] for r in recs) / len(recs)
