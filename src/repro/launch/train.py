"""Training launcher: local meshes for real runs, with fault-tolerant
checkpointing, health monitoring, and elastic recovery wired in.

  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b \
      --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

This is a thin adapter over the shared training engine (train/loop.py,
DESIGN.md §6): the raw sharded step from ``parallel/api`` is scanned
into jitted multi-step chunks with donated state, and batches come from
ONE source of truth — ``SyntheticTokens.batch(step)``, a pure function
of the global step (restart-deterministic) — stacked per chunk and
prefetched on a background thread while the previous chunk computes.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.common.partitioning import DEFAULT_RULES
from repro.configs import registry
from repro.data import tokens as token_data
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.obs import log as obs_log
from repro.obs.trace import TRACER
from repro.parallel import api
from repro.train import loop

_LOG = obs_log.get_logger("train")


def train_loop(cfg, mesh, *, steps: int, seq_len: int, global_batch: int,
               ckpt_dir=None, ckpt_every: int = 50, rules=None,
               train_cfg: api.TrainConfig = None, log_every: int = 10,
               seed: int = 0, on_step=None, chunk_steps: int = 16,
               metrics_out: str | None = None):
    rules = rules or DEFAULT_RULES.copy_with()
    train_cfg = train_cfg or api.TrainConfig()
    example = {"batch": {"tokens": jax.ShapeDtypeStruct(
        (global_batch, seq_len), np.int32)}}
    raw_step, sh = api.build_train_step(cfg, mesh, rules,
                                        train_cfg=train_cfg,
                                        example_batch=example)
    params = api.init_params(cfg, seed=seed, mesh=mesh, rules=rules)
    state = api.make_train_state(
        params, compression=train_cfg.compression is not None)
    state = jax.device_put(state, sh["state"])

    # One source of truth for data: batch(step) is recomputable from the
    # step index alone, so a resumed run sees the exact stream it would
    # have seen uninterrupted. The engine stacks chunk_steps batches and
    # prefetches them (tokens.Prefetcher) while the current chunk runs.
    src = token_data.SyntheticTokens(token_data.DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq_len,
        global_batch=global_batch, seed=seed))

    # health stack comes from the engine defaults: its own registry owns
    # the per-host step histograms (health.step_s.<host>) and the
    # silent-host gauge — DESIGN.md §8
    engine = loop.TrainEngine(
        loop.EngineConfig(steps=steps, chunk_steps=chunk_steps,
                          ckpt_dir=ckpt_dir, ckpt_every=ckpt_every),
        lambda state, step, batch: raw_step(state, batch),
        host_batch_fn=src.batch,
        state_shardings=sh["state"], batch_shardings=sh["batch"])

    losses = []

    def on_metrics(step, row, st):
        losses.append(row["loss"])
        if on_step:
            on_step(step, row["loss"], st)
        if step % log_every == 0:
            _LOG.info("step", step=step, loss=round(float(row["loss"]), 4),
                      dt_ms=round(row["dt"] * 1e3))

    state, _ = engine.run(state, on_metrics=on_metrics)
    if metrics_out:
        with open(metrics_out, "w") as f:
            f.write(engine.obs.to_json())
        _LOG.info("metrics_written", path=metrics_out)
    return state, losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data", type=int, default=None,
                    help="data-axis size (default: every local device "
                         "the model axis leaves)")
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--chunk-steps", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", default=None,
                    choices=[None, "topk", "int8"])
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON (train.chunk events)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine metrics snapshot JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.trace_out:
        TRACER.enable()
    cfg = (registry.reduced_config(args.arch) if args.reduced
           else registry.get_config(args.arch))
    mesh = make_local_mesh(args.data, args.model)
    tc = api.TrainConfig(num_microbatches=args.microbatches,
                         compression=args.compression)
    _, losses = train_loop(cfg, mesh, steps=args.steps, seq_len=args.seq,
                           global_batch=args.batch,
                           ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every,
                           chunk_steps=args.chunk_steps, train_cfg=tc,
                           metrics_out=args.metrics_out)
    _LOG.info("trained", loss_first=round(float(losses[0]), 4),
              loss_last=round(float(losses[-1]), 4), n_steps=len(losses))
    if args.trace_out:
        TRACER.export(args.trace_out)
        _LOG.info("trace_written", path=args.trace_out,
                  n_events=len(TRACER.events()))


if __name__ == "__main__":
    main()
