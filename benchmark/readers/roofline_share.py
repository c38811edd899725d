"""The kernels' share of the HBM roofline over the traced span, in
percent: the least bytes the span's completed requests had to read
(``roofline.py`` over the generated data) over the peak bandwidth of this
device kind (``peaks.json``) times the device's busy seconds.  No busy
time or no completed request: nothing, never 0."""

import roofline


def read(spec: dict, ctx: dict):
    t = ctx.get("trace")
    span = ctx["spans"].get("trace")
    if not t or not span or not span.get("least_bytes") or t["busy_s"] <= 0:
        return None
    bw = roofline.peak(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * span["least_bytes"] / (bw * t["busy_s"])
