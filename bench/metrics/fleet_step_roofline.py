"""Share of the HBM roofline of all the cell's chips that the fleet's
vmapped dense OGB step reaches: the least bytes of a step over a tenant's
window, times the tenant-windows replayed, over the chips' summed peak
bandwidth, against the device busy time (the per-chip average).  At one
chip it is ``dense_step_roofline``; the fleet's windows count the
tenant-windows of every chip, so the peak is every chip's."""

from bench import roofline


def read(ctx):
    red, s, cfg = ctx["trace"], ctx["stats"], ctx["cfg"]
    if s.windows <= 0 or red["busy_s"] <= 0:
        return None
    least = s.windows * roofline.dense_step_bytes(cfg["catalog_size"], cfg["window"])
    peak = int(ctx["cell"]["chips"]) * roofline.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (peak * red["busy_s"])
