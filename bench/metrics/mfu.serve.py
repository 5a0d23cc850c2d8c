"""The served field's share of the devices' peak, in %: the field's
operations per point (grid encode, direction encode and MLPs, from the
configuration's shapes) times the points of the valid pixels held in the
window, over the window, the chips and the peak bf16 FLOP/s."""
from bench import work


def read(ctx):
    c = ctx.counts
    points = c["held_pixels"] * c["n_samples"]
    if not points:
        return None
    flops = work.field_flops(ctx.cell.config, points)
    return 100.0 * flops / (c["window_s"] * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])
