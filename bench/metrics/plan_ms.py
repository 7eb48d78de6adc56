"""Host planner: ``timings["plan_ms"]`` (tuple sets, CN enumeration, routing
plans, or a plan-cache hit), summed over the window per completed request."""


def read(ctx):
    recs = ctx["records"]
    if not recs:
        return None
    return sum(r.response.timings["plan_ms"] for r in recs) / len(recs)
