"""The encode's share of its roofline in the served tiles, in %: the
least time of one tile's grid encode, and direction encode where the field
has one, on one device (the larger of its operations over the peak and
its bytes over the bandwidth, bench/work.py) times the tiles held in the
window, over the device time of the ops in the ``encode`` scope, averaged
over the devices."""
from bench import work


def read(ctx):
    c, r = ctx.counts, ctx.reduced
    busy = (r or {}).get("phase_s", {}).get("encode")
    if not busy or not c["held_requests"]:
        return None
    points = c["tile_pixels"] * c["n_samples"] // ctx.chips
    least, _ = work.least_time(*work.field_encode(ctx.cell.config, points),
                               ctx.peaks)
    return 100.0 * c["held_requests"] * least / busy
