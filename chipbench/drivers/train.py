"""Training cells: a closed loop of ``TrainStep`` steps, each on a fresh
batch of seeded random token ids fetched from a host-side generator.

A step is fetched, run and waited for (``block_until_ready`` of the updated
parameters) before the next begins, so the host's clock around a step is the
step. Random tokens cannot be learned: the loss stays near ln(vocab), and
the check is agreement with the reference and finite losses, not a falling
loss.
"""

from __future__ import annotations

import math
import time
from statistics import median
from typing import Dict, List

from .. import model_math, traffic
from .common import (CompileCounter, Context, Heartbeat, Result, Slice,
                     family, memory_peak_bytes, model_sizes, sized)

UNITS = {"train_tokens_per_s": "tokens/s", "setup_s": "s"}


def run(ctx: Context) -> Result:
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.api import TrainStep
    parts: Dict[str, float] = {}
    t = time.perf_counter()
    parts["imports"] = t - ctx.t_process
    config = sized(ctx.config, ctx.rehearse)
    sizes = model_sizes(config)
    cell = sized(ctx.cell, ctx.rehearse)
    spec, opts = cell["traffic"], config["train"]
    fam = family(config)
    model, cfg, weights = fam.build_model(sizes, ctx.seed, opts)
    model.train()
    crit = fam.build_criterion(cfg)
    if opts["optimizer"] != "AdamW":
        raise ValueError(f"unknown optimizer {opts['optimizer']!r}")
    opt = paddle.optimizer.AdamW(
        learning_rate=opts["learning_rate"],
        weight_decay=opts["weight_decay"], parameters=model.parameters())
    train = TrainStep(model, lambda logits, labels: crit(logits, labels), opt)
    params = list(model.parameters())
    jax.block_until_ready(list(weights.values()))
    parts["weights"] = time.perf_counter() - t

    seqs, toks = spec["sequences_per_step"], spec["sequence_tokens"]
    batches = traffic.token_batches(ctx.seed, cfg.vocab_size, seqs, toks)

    def fetch():
        with jax.profiler.TraceAnnotation("chipbench.fetch"):
            return Tensor(jnp.asarray(next(batches)))

    def step(ids):
        with jax.profiler.TraceAnnotation("chipbench.train_step"):
            loss = train((ids,), (ids,))
            jax.block_until_ready([p._data for p in params])
        return loss._data

    # the reference first: the first step donates the weights it reads
    t = time.perf_counter()
    first = fetch()
    ref_losses = [fam.loss(sizes, weights, row,
                           cell["check"]["reference_query_block"])
                  for row in jax.device_get(first._data)]
    ref_loss = sum(ref_losses) / len(ref_losses)
    del weights
    parts["reference"] = time.perf_counter() - t
    t = time.perf_counter()
    first_loss = float(step(first))
    for _ in range(spec["warmup_steps"] - 1):
        step(fetch())
    parts["compile_and_warmup"] = time.perf_counter() - t

    compiles = CompileCounter()
    t_window = time.perf_counter()
    setup_s = t_window - ctx.t_process
    tracer = Slice(ctx, t_window, cell["trace_slice_s"])
    steps: List[Dict] = []
    losses = []
    compiles.armed = True
    heart = Heartbeat().start(ctx.heartbeat)
    while True:
        now = time.perf_counter()
        if now >= t_window + ctx.seconds:
            break
        tracer.tick(now)
        t_begin = time.perf_counter()
        ids = fetch()
        losses.append(step(ids))
        steps.append({"t_begin": t_begin, "t_end": time.perf_counter()})
    t_done = time.perf_counter()
    stops = heart.stop()
    compiles.armed = False
    tracer.finish()

    losses = [float(x) for x in losses]
    tokens = len(steps) * seqs * toks
    rate = tokens / (t_done - t_window)
    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    finite = all(math.isfinite(x) for x in losses + [first_loss])
    check = {"ok": bool(rel <= cell["check"]["loss_rel_tol"] and finite),
             "reference_loss": ref_loss, "model_loss": first_loss,
             "loss_rel_diff": rel, "loss_rel_tol": cell["check"]["loss_rel_tol"],
             "losses_finite": finite, "ln_vocab": math.log(cfg.vocab_size)}
    ctx.emit("check", **check)
    ctx.emit("setup", setup_s=setup_s, parts=parts)
    step_ms = [(s["t_end"] - s["t_begin"]) * 1e3 for s in steps]
    flops = model_math.train_flops_per_token(sizes, toks)
    notes = {"compiles_in_window": compiles.count, "steps_in_window": len(steps),
             "tokens_in_window": tokens, "window_s": t_done - t_window,
             "step_ms_p50": median(step_ms) if step_ms else None,
             "first_loss": first_loss, "last_loss": losses[-1] if losses else None,
             "model_flops_per_token": flops,
             "params": sum(int(p._data.size) for p in params)}
    if not ctx.rehearse:
        notes["model_flops_utilization_pct"] = 100 * flops * rate / (
            model_math.peaks(ctx.device_kind)["bf16_flops_per_s"])
    traced = [s for s in steps if tracer.covers(s["t_begin"], s["t_end"])]
    notes["traced_steps"] = len(traced)
    ctx.emit("notes", **notes, **stops)
    return Result(
        correct=bool(check["ok"] and compiles.count == 0 and steps),
        attempted=len(steps),
        failed=sum(not math.isfinite(x) for x in losses),
        end_to_end={"train_tokens_per_s": (rate, UNITS["train_tokens_per_s"]),
                    "setup_s": (setup_s, UNITS["setup_s"])},
        steps=steps, traced_steps=traced,
        reduced=tracer.reduce("chipbench.train_step"), config=sizes,
        cell=cell, device_kind=ctx.device_kind,
        memory_peak_bytes=memory_peak_bytes(cell["chips"]),
        compared={"loss_rel_diff": [rel, cell["check"]["loss_rel_tol"]],
                  "losses_not_finite": [
                      sum(not math.isfinite(x)
                          for x in losses + [first_loss]), 0],
                  "compiles_in_window": [compiles.count, 0]})
