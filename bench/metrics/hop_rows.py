"""Device body: the padded rows the ``fct.hop`` stage reads (a snowflake
sub-dimension and its parent, per CN slot of each dispatch), from the
``hop_rows`` argument of each dispatch's ``engine.enqueue`` span, summed
over the window's distinct spans per completed request.

Engine spans land only on a batch leader's trace, once per shared
dispatch, so a dispatch shared by two requests counts once.  A program
whose spans carry no ``hop_rows`` reads nothing."""

SPAN = "engine.enqueue"


def read(ctx):
    recs = ctx["records"]
    spans = {s for r in recs if r.response.trace is not None
             for s in r.response.trace.spans()
             if s.name == SPAN and "hop_rows" in s.args}
    if not spans:
        return None
    return sum(int(s.args["hop_rows"]) for s in spans) / len(recs)
