"""Field training through ``core.train.train_field`` on its TrainEngine.

Traffic parameters (``bench/traffic/<name>.json``): ``batch_rays`` rays
of ``n_samples`` samples per step against ``gt_samples``-sample analytic
targets of the training ``camera`` (the trainer's own), ``chunk_steps``
steps per engine call, the ``adam`` settings, and ``checked_steps``: the
first steps, run in set-up through the window's own call and feed, that
the reference follows after the window.

Set-up runs the first ``checked_steps`` steps (the first compiles) and
keeps the optimizer's first moment after step one and the parameters
after the last of them. The window then counts the steps that complete
until ``--seconds`` have passed; ``train_rays_per_s`` is their rays over
the time from the window's start to the end of the last of them.

The check, after the window: each checked step's loss, the first
gradient as Adam received it (its first moment over 1 - b1), and the
parameters' change over the checked steps, against the reference from the
same seed; gradients and changes are compared by the worst leaf.
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness


class _WindowClosed(Exception):
    pass


# the engine's private method that builds a chunk's program, as the program
# names it today: a traced run keeps the chunk it returns for the trace's
# phase map, since the engine offers no public way to its compiled program
CHUNK_HOOK = "_chunk_fn"


def _chunk_builder(loop):
    if not hasattr(loop.TrainEngine, CHUNK_HOOK):
        raise RuntimeError(
            f"TrainEngine has no {CHUNK_HOOK}: the traced run cannot find "
            "the compiled chunk for its phase map")
    return getattr(loop.TrainEngine, CHUNK_HOOK)


def _copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.copy, tree)


def run(ctx) -> "harness.Outcome":
    import jax
    from repro.core import train as train_mod
    from repro.train import loop, optim

    t = ctx.traffic
    cfg = harness.field_config(ctx.config)
    n_check = t["checked_steps"]
    rays = t["batch_rays"]
    steps_total = t["chunk_steps"] * 10 ** 6
    losses, saved = [], {}
    chunks = []
    state = {"done": 0, "t_last": None, "nonfinite": 0, "deadline": None}

    def on_metrics(i, row, st):
        if not np.isfinite(row["loss"]):
            state["nonfinite"] += 1
        if i < n_check:
            losses.append(row["loss"])
            if i == 0:
                ctx.mark("first step")
                saved["mu"] = _copy(st["opt"].mu)
                saved["shapes"] = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)
            if i == n_check - 1:
                saved["params"] = _copy(st["params"])
                jax.block_until_ready(saved["params"])
                state["deadline"] = ctx.open_window() + ctx.seconds
            return
        now = time.perf_counter()
        if now > state["deadline"]:
            ctx.close_window(state["deadline"])
            raise _WindowClosed
        state["done"] += 1
        state["t_last"] = now
        ctx.done(now)

    chunk_fn = _chunk_builder(loop) if ctx.trace else None
    if ctx.trace:
        def keep_chunk(self, n):
            fn = chunk_fn(self, n)
            chunks.append(fn)
            return fn
        setattr(loop.TrainEngine, CHUNK_HOOK, keep_chunk)
    try:
        train_mod.train_field(
            cfg, steps=steps_total, batch_size=rays,
            seed=harness.program_seed(ctx.seed),
            chunk_steps=t["chunk_steps"],
            opt_cfg=optim.AdamConfig(**t["adam"]),
            on_metrics=on_metrics, n_samples=t["n_samples"],
            gt_samples=t["gt_samples"])
        raise RuntimeError("training ended before the window closed")
    except _WindowClosed:
        pass
    finally:
        if ctx.trace:
            setattr(loop.TrainEngine, CHUNK_HOOK, chunk_fn)
    ctx.end_window_work()
    hlo = []
    if ctx.trace:
        import jax.numpy as jnp
        hlo = [chunks[0].lower(saved["shapes"], jnp.int32(0)).compile()
               .as_text()]
    reduced = ctx.read_trace(hlo) if ctx.trace else None
    peak = harness.memory_peak(ctx.devices)

    span = (state["t_last"] - ctx.t_open) if state["done"] else float("nan")
    checks, extra = check(ctx, cfg, losses, saved)
    counts = {"steps": state["done"], "train_s": span,
              "rays_per_step": rays, "n_samples": t["n_samples"], **extra}
    values = {"train_rays_per_s": state["done"] * rays / span,
              "setup_s": ctx.t_open - ctx.t_start}
    return harness.Outcome(
        attempted=state["done"], failed=state["nonfinite"], values=values,
        checks=checks, counts=counts, memory_peak_bytes=peak,
        reduced=reduced)


# ----------------------------------------------------------- reference
def leaf_norms(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64).ravel())) for p, x in flat}


def leaf_gaps(got: dict, want: dict, leaves=None) -> dict:
    """|norm(got) - norm(want)| of each leaf, against the larger of its
    reference norm and the median leaf's."""
    leaves = sorted(want) if leaves is None else leaves
    floor = float(np.median([want[k] for k in leaves]))
    return {k: abs(got[k] - want[k]) / max(want[k], floor) for k in leaves}


def worst_leaf_gap(got: dict, want: dict, leaves=None) -> float:
    return max(leaf_gaps(got, want, leaves).values())


def reference_steps(ctx, n_steps: int, precision: str, ray_share=1.0):
    """Losses, first gradient's leaf norms and the change's leaf norms of
    the reference over ``n_steps`` steps from the seed. ``ray_share`` < 1
    keeps that share of each batch's rays (a planted fault)."""
    import jax
    import jax.numpy as jnp
    from bench.reference import field

    t = ctx.traffic
    cfg = ctx.config
    ref = harness.reference(cfg)
    cam = t["camera"]
    intr = (float(cam["height"]), float(cam["width"]), float(cam["focal"]))
    c2w = field.look_at(cam["eye"])
    keep = int(t["batch_rays"] * ray_share)
    k_init, k_data = jax.random.split(harness.base_key(ctx.seed))

    @jax.jit
    def step(w, mu, nu, i):
        b = ref.batch(jax.random.fold_in(k_data, i), intr, c2w,
                      t["batch_rays"], t["gt_samples"])
        b = jax.tree.map(lambda x: x[:keep], b)
        loss, g = jax.value_and_grad(ref.loss)(w, cfg, b, t["n_samples"],
                                              precision)
        w, mu, nu = ref.adam(w, g, mu, nu, (i + 1).astype(jnp.float32),
                             t["adam"])
        return w, mu, nu, loss, g

    w0 = jax.jit(lambda k: ref.init_weights(k, cfg))(k_init)
    zeros = jax.tree.map(jnp.zeros_like, w0)
    w, mu, nu = w0, zeros, zeros
    losses, g0 = [], None
    for i in range(n_steps):
        w, mu, nu, loss, g = step(w, mu, nu, jnp.int32(i))
        losses.append(float(loss))
        if i == 0:
            g0 = leaf_norms(g)
        del g
    change = leaf_norms(jax.tree.map(jnp.subtract, w, w0))
    return losses, g0, change


def gaps(got, want) -> dict:
    """The three numbers compared, of a run ``got`` against ``want``,
    each a (losses, first-gradient norms, change norms) triple."""
    l_got, g_got, c_got = got
    l_want, g_want, c_want = want
    median = float(np.median(list(g_want.values())))
    moved = [k for k in sorted(g_want) if g_want[k] >= 1e-3 * median]
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(l_got, l_want)),
        "grad_gap": worst_leaf_gap(g_got, g_want),
        "change_gap": worst_leaf_gap(c_got, c_want, moved),
    }


def check(ctx, cfg, losses, saved):
    """The checks of the program's first steps, and any extra readings.
    With ``ctx.control`` the control's steps stand in for the program's,
    and the program's numbers, each leaf's readings and half the batch
    left out are extra readings."""
    import jax
    import jax.numpy as jnp
    from repro.common.param import unbox
    from repro.core import fields

    t = ctx.traffic
    n = t["checked_steps"]
    k_init, _ = jax.random.split(harness.base_key(ctx.seed))
    p0 = jax.jit(lambda k: unbox(fields.init_field(k, cfg))[0])(k_init)
    b1 = t["adam"]["b1"]
    got = (losses,
           leaf_norms(jax.tree.map(lambda m: m / (1 - b1), saved["mu"])),
           leaf_norms(jax.tree.map(jnp.subtract, saved["params"], p0)))
    del p0, saved["mu"], saved["params"]
    want = reference_steps(ctx, n, "highest")
    found = gaps(got, want)
    extra = {}
    if ctx.control:
        # each leaf's readings, to see which leaf a number swings with
        for i, what in ((1, "grad"), (2, "change")):
            for leaf, v in leaf_gaps(got[i], want[i]).items():
                extra[f"program.{what}{leaf}"] = v
                extra[f"reference.{what}_norm{leaf}"] = want[i][leaf]
        extra.update({f"program.{k}": v for k, v in found.items()})
        half = reference_steps(ctx, n, "highest", ray_share=0.5)
        extra.update({f"half_batch.{k}": v
                      for k, v in gaps(half, want).items()})
        found = gaps(reference_steps(ctx, n, "high"), want)
    limits = ctx.limits
    return {k: (v, limits[k]) for k, v in found.items()}, extra
