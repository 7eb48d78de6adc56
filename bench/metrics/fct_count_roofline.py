"""Kernel: ``fct_count``'s share of its roofline, in percent.

The least time is the work the requests' histograms need over the chip's
HBM bandwidth (``bench/peaks.json``): every joined CN relation's tuple-set
rows read once (``text_len`` int32 token ids and one weight each) and its
vocab bins written once, counted from the reference's tuple sets, not from
the kernel's grid, limbs or padding.  The histogram does no arithmetic worth
the name (one add per token), so it is bytes-bound.  Share = least time /
measured ``fct_count`` time."""
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def read(ctx):
    prof = ctx["profile"]
    if prof is None or not prof["kernel_events"] or prof["fct_count_s"] <= 0:
        return None
    peaks = json.loads(PEAKS.read_text())
    if ctx["device_kind"] not in peaks:
        raise KeyError(f"no peaks for device {ctx['device_kind']!r} in "
                       f"{PEAKS.name}")
    least_s = ctx["work"]["bytes"] / peaks[ctx["device_kind"]][
        "hbm_bytes_per_s"]
    return 100.0 * least_s / prof["fct_count_s"]
