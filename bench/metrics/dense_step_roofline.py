"""Share of the HBM roofline the dense OGB step reaches: the least bytes
of a step over the chip's peak bandwidth, against the device time per
window.  Bandwidth bounds the step; its operations are a few per byte."""

from bench import roofline


def read(ctx):
    red, s, cfg = ctx["trace"], ctx["stats"], ctx["cfg"]
    if s.windows <= 0 or red["busy_s"] <= 0:
        return None
    least = roofline.dense_step_bytes(cfg["catalog_size"], cfg["window"])
    least_s = least / roofline.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (red["busy_s"] / s.windows)
