"""The trained field's share of the devices' peak, in %: the field's
forward and backward operations per point (grid encode, direction encode
and MLPs, from the configuration's shapes; recomputed work does not
count) times the points of the steps completed in the window, over their
time, the chips and the peak bf16 FLOP/s."""
from bench import work


def read(ctx):
    c = ctx.counts
    points = c["steps"] * c["rays_per_step"] * c["n_samples"]
    if not points:
        return None
    flops = work.field_flops(ctx.cell.config, points, backward=True)
    return 100.0 * flops / (c["train_s"] * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])
