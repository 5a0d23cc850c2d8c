"""The MLPs' share of their roofline in the served tiles, in %: the least
time of one tile's MLPs on one device (bench/work.py) times the tiles held
in the window, over the device time of the ops in the ``mlp`` scope,
averaged over the devices."""
from bench import work


def read(ctx):
    c, r = ctx.counts, ctx.reduced
    busy = (r or {}).get("phase_s", {}).get("mlp")
    if not busy or not c["held_requests"]:
        return None
    cfg = ctx.cell.config
    points = c["tile_pixels"] * c["n_samples"] // ctx.chips
    least, _ = work.least_time(work.mlp_flops(cfg, points),
                               work.mlp_bytes(cfg, points), ctx.peaks)
    return 100.0 * c["held_requests"] * least / busy
