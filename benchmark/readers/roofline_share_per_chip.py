"""The kernels' share of the HBM roofline over the traced span on a mesh,
in percent: the least bytes the span's completed requests had to read
(``roofline.py`` over the generated data, every shard on whichever chip
holds it) over the peak bandwidth of ONE chip of this device kind
(``peaks.json``) times the chips in the recording times their mean busy
seconds (``trace_reduce.reduce`` averages busy time over the device
planes and says how many there were).  With one chip this is
``roofline_share``'s number; with four, the same bytes over four times
the bandwidth.  No busy time or no completed request: nothing, never 0."""

import roofline


def read(spec: dict, ctx: dict):
    t = ctx.get("trace")
    span = ctx["spans"].get("trace")
    if not t or not span or not span.get("least_bytes") or t["busy_s"] <= 0:
        return None
    bw = roofline.peak(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * span["least_bytes"] / (bw * t["devices"] * t["busy_s"])
