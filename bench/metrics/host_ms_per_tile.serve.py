"""Host milliseconds per request inside ``RenderEngine.submit``: its
request preparation and its dispatch, from the engine's own
``serve.submit_s.*`` and ``serve.dispatch_s.*`` histograms over the
window. Blocking on earlier requests is not in it."""


def read(ctx):
    n = ctx.counts.get("submits")
    if not n:
        return None
    return ctx.counts["host_s"] / n * 1e3
