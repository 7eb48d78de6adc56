"""Runtime: the null CN slots the per-CN family runs beyond each dispatch
group's real CNs (``engine.pad_slots``: ``n_stack - len(group)``), from
the ``pad_slots`` argument of each dispatch's ``engine.enqueue`` span,
summed over the window's distinct spans per completed request.

A null slot routes nothing and adds exactly zero to every histogram, but
the vmapped device body still runs MR¹ and MR² over it.  Engine spans land
only on a batch leader's trace, once per shared dispatch, so a dispatch
shared by two requests counts once.  A program whose spans carry no
``pad_slots`` reads nothing."""

SPAN = "engine.enqueue"


def read(ctx):
    recs = ctx["records"]
    spans = {s for r in recs if r.response.trace is not None
             for s in r.response.trace.spans()
             if s.name == SPAN and "pad_slots" in s.args}
    if not spans:
        return None
    return sum(int(s.args["pad_slots"]) for s in spans) / len(recs)
