"""Device: share of the traced window in which no op ran on the device
(1 - union of op intervals / window), in percent."""


def read(ctx):
    prof = ctx["profile"]
    if prof is None or prof["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - prof["busy_s"] / prof["window_s"])
