"""Device body: device time of every op in the traced window that is not
the ``fct_count`` kernel (gathers, scatter-adds, reductions, copies), per
completed request."""


def read(ctx):
    prof = ctx["profile"]
    if not ctx["records"] or prof is None:
        return None
    return prof["xla_ops_s"] * 1e3 / len(ctx["records"])
