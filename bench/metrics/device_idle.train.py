"""Share of the traced window in which no operation ran on the device, in
%: one minus the union of the device's op intervals over the window,
averaged over the cell's devices."""


def read(ctx):
    if ctx.reduced is None or not ctx.reduced["n_ops"]:
        return None
    return 100.0 * ctx.reduced["idle_share"]
