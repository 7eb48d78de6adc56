"""Per-layer metric readers: ``<metric>.read(ctx)`` returns the metric's
value over the traced window, or None when there is nothing to read.

``ctx`` holds ``records`` (the completed requests, each with the program's
response), ``profile`` (``trace_reduce.reduce_trace``'s
numbers, or None), ``work`` (the histogram work the requests need, from the
reference's tuple sets) and ``device_kind``.
"""
