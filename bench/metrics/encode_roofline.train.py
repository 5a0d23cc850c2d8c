"""The encode's share of its roofline in the train steps, in %: the
least time of one step's grid encode, forward and backward (the sum of the
table gradient), and direction encode where the field has one, on one
device (bench/work.py) times the steps completed in the window, over the
device time of the ops in the ``encode`` scope and its transpose."""
from bench import work


def read(ctx):
    c, r = ctx.counts, ctx.reduced
    busy = (r or {}).get("phase_s", {}).get("encode")
    if not busy or not c["steps"]:
        return None
    points = c["rays_per_step"] * c["n_samples"] // ctx.chips
    least, _ = work.least_time(
        *work.field_encode(ctx.cell.config, points, backward=True),
        ctx.peaks)
    return 100.0 * c["steps"] * least / busy
