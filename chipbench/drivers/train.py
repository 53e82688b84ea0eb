"""Training cells: a closed loop of ``TrainStep`` steps, each on a fresh
batch of seeded random token ids fetched from a host-side generator.

In the window the host keeps ``dispatch_ahead_s`` seconds of steps launched
ahead of the one it waits for, as a training loop that reads its losses late
does: a host that stands still for less than that leaves the chip fed. The
runtime may queue fewer: a launch behind as many as it holds waits for room
(each step records ``queued``, the steps in flight at its launch). When
the window's time is up nothing more is launched, every launched step is
waited for, and the clock is read after that wait: the rate is all of those
steps over all of that time. Random tokens cannot be learned: the loss stays
near ln(vocab), and the check is agreement with the reference and finite
losses, not a falling loss.
"""

from __future__ import annotations

import collections
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

    def launch(ids):
        with jax.profiler.TraceAnnotation("chipbench.train_step"):
            return train((ids,), (ids,))._data

    def step(ids):
        loss = launch(ids)
        jax.block_until_ready([p._data for p in params])
        return loss

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
        t_step = time.perf_counter()
        step(fetch())
    # the last warm-up step is a compiled one: it sets how many steps are
    # launched ahead in the window
    ahead = max(1, math.ceil(cell["dispatch_ahead_s"]
                             / (time.perf_counter() - t_step)))
    parts["compile_and_warmup"] = time.perf_counter() - t

    compiles = CompileCounter()
    t_window = time.perf_counter()
    setup_s = t_window - ctx.t_process
    # the slice is due ``dispatch_ahead_s`` early: the steps in flight are
    # waited for first, so that it still holds ``trace_slice_s`` of launches
    tracer = Slice(ctx, t_window,
                   cell["trace_slice_s"] + cell["dispatch_ahead_s"])
    steps: List[Dict] = []
    losses = []
    waiting = collections.deque()    # indices of launched, unwaited steps

    def wait_one():
        i = waiting.popleft()
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            jax.block_until_ready(losses[i])
        steps[i]["t_done"] = time.perf_counter()

    compiles.armed = True
    heart = Heartbeat().start(ctx.heartbeat)
    while True:
        now = time.perf_counter()
        if now >= t_window + ctx.seconds:
            break
        if tracer.due(now):
            # the slice begins with nothing in flight, so that the device
            # runs in it the steps launched in it
            while waiting:
                wait_one()
        tracer.tick(now)
        t_begin = time.perf_counter()
        queued = len(waiting)
        losses.append(launch(fetch()))
        steps.append({"t_begin": t_begin, "queued": queued})
        waiting.append(len(steps) - 1)
        if len(waiting) > ahead:
            wait_one()
        steps[-1]["t_end"] = time.perf_counter()
    while waiting:
        wait_one()
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
    done = [s["t_done"] for s in steps]
    step_ms = [(b - a) * 1e3 for a, b in zip(done, done[1:])]
    flops = model_math.train_flops_per_token(sizes, toks)
    notes = {"compiles_in_window": compiles.count, "steps_in_window": len(steps),
             "tokens_in_window": tokens, "window_s": t_done - t_window,
             "steps_ahead": ahead,
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
        reduced=tracer.reduce(None), config=sizes,
        cell=cell, device_kind=ctx.device_kind,
        memory_peak_bytes=memory_peak_bytes(cell["chips"]),
        compared={"loss_rel_diff": [rel, cell["check"]["loss_rel_tol"]],
                  "losses_not_finite": [
                      sum(not math.isfinite(x)
                          for x in losses + [first_loss]), 0],
                  "compiles_in_window": [compiles.count, 0]})
