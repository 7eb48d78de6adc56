"""Kernel: device time of the ``fct_count`` Mosaic kernel events in the
traced window, per completed request."""


def read(ctx):
    prof = ctx["profile"]
    if not ctx["records"] or prof is None or not prof["kernel_events"]:
        return None
    return prof["fct_count_s"] * 1e3 / len(ctx["records"])
