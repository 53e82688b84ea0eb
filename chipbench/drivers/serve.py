"""Serving cells: ``ContinuousBatchingEngine`` under open-loop or
closed-loop traffic, one process, one thread.

The loop is the server a user would write around the engine: hand over the
requests that are due, call ``engine.step()``, read which rows got a token.
Every time is the host's clock after a step has returned, which is after the
sampled tokens have come back from the device. A request's clock starts when
it was due, not when the loop got round to adding it.
"""

from __future__ import annotations

import time
from statistics import median
from typing import Dict, List, Optional

import numpy as np

from .. import reference, traffic
from .common import (CompileCounter, Context, Result, Slice, build_model,
                     model_sizes, quantile, sized)

UNITS = {"ttft_p50_ms": "ms", "ttft_mean_ms": "ms", "itl_p95_ms": "ms",
         "serve_tokens_per_s": "tokens/s", "setup_s": "s"}


class _LogitTap:
    """Stands where the engine holds its model and keeps each step's
    logits, for the check. The engine calls it and reads ``config``."""

    def __init__(self, model):
        self._model = model
        self.config = model.config
        self.logits: List = []

    def __call__(self, *args, **kwargs):
        out = self._model(*args, **kwargs)
        self.logits.append(out._data)
        return out


class _Sent:
    """One request as the driver follows it."""
    __slots__ = ("plan", "due", "req", "error", "stamps")

    def __init__(self, plan: traffic.Planned, due: float):
        self.plan = plan
        self.due = due
        self.req = None
        self.error: Optional[str] = None
        self.stamps: List[float] = []       # when each output token came


class Session:
    """Model, engine and check, built once; then any number of phases."""

    def __init__(self, ctx: Context):
        import jax
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        from paddle_tpu.observability import metrics
        self.ctx = ctx
        self.parts: Dict[str, float] = {}
        t = time.perf_counter()
        self.parts["imports"] = t - ctx.t_process
        config = sized(ctx.config, ctx.rehearse)
        self.sizes = model_sizes(config)
        self.cell = sized(ctx.cell, ctx.rehearse)
        self.model, self.cfg, self.weights = build_model(self.sizes, ctx.seed)
        self.model.eval()
        jax.block_until_ready(list(self.weights.values()))
        self.parts["weights"] = time.perf_counter() - t
        t = time.perf_counter()
        self.eng = ContinuousBatchingEngine(self.model, **config["engine"])
        self.step_tokens = metrics.registry().get("serving.step_tokens")
        self.compiles = CompileCounter()
        self.sent: Dict[int, _Sent] = {}       # rid -> request in flight
        self.check = self._check()
        self.parts["engine_check_warmup"] = time.perf_counter() - t

    # -- correctness, outside any window -------------------------------------
    def _check(self) -> Dict:
        """One seeded prompt through the engine's own path: chunked prefill,
        then decode steps through the paged cache; every position's logits
        against the reference's full forward over the same tokens."""
        import jax.numpy as jnp
        spec = self.cell["check"]
        rng = np.random.default_rng([self.ctx.seed, 1])
        prompt = rng.integers(0, self.cfg.vocab_size, spec["prompt_tokens"])
        tap = _LogitTap(self.model)
        self.eng.model = tap
        try:
            rid = self.eng.add_request(prompt,
                                       max_new_tokens=spec["decode_steps"])
            req = self.eng.results[rid]
            rows, ctx_before = [], 0
            while not req.done:
                self.eng.step()
                n = req.ctx - ctx_before       # tokens this step packed
                rows.append(tap.logits.pop()[0, :n].astype(jnp.float32))
                ctx_before = req.ctx
        finally:
            self.eng.model = self.model
        self.eng.pop_result(rid)
        got = jnp.concatenate(rows)
        out = list(req.out_tokens)
        ids = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        want = reference.logits(self.sizes, self.weights, ids,
                                query_block=512)
        scale = float(jnp.max(jnp.abs(want)))
        gap = float(jnp.max(jnp.abs(got - want))) / scale
        # Greedy decoding must agree with the reference wherever the
        # reference's lead over its runner-up is wider than the gap allows
        top2 = jnp.sort(want[len(prompt) - 1:], axis=-1)[:, -2:]
        lead = np.asarray(top2[:, 1] - top2[:, 0]) / scale
        picks = np.asarray(jnp.argmax(want[len(prompt) - 1:], -1))
        greedy_ok = all(int(picks[i]) == out[i] for i in range(len(out))
                        if lead[i] > 2 * spec["logit_gap_tol"])
        ok = (got.shape == want.shape and gap <= spec["logit_gap_tol"]
              and greedy_ok and len(out) == spec["decode_steps"])
        return {"ok": bool(ok), "logit_gap": gap,
                "logit_gap_tol": spec["logit_gap_tol"],
                "positions": int(got.shape[0]), "greedy_ok": bool(greedy_ok),
                "ref_logit_max": scale}

    # -- one phase of traffic ------------------------------------------------
    def phase(self, traffic_spec: Dict, seconds: float, phase_seed: int,
              tracer: Optional[Slice] = None, start_at: Optional[float] = None
              ) -> Dict:
        """Offers ``traffic_spec`` for ``seconds`` from ``start_at`` (default:
        now). Requests already in flight stay; none is drained at the end.
        Returns the phase's records."""
        import jax
        eng = self.eng
        rng = np.random.default_rng([self.ctx.seed, phase_seed])
        closed = traffic_spec["arrivals"] == "closed"
        clients = int(traffic_spec["clients"]) if closed else 0

        def more() -> List[traffic.Planned]:
            """Open loop: the phase's whole schedule. Closed loop: four
            requests a client, planned again whenever they run out."""
            return traffic.plan(traffic_spec, seconds, self.cfg.vocab_size,
                                rng, requests=4 * clients)

        plans = more()
        t0 = time.perf_counter() if start_at is None else start_at
        t1 = t0 + seconds
        mine: List[_Sent] = []
        steps: List[Dict] = []
        late: List[float] = []
        next_i = 0

        def send(plan: traffic.Planned, due: float) -> None:
            s = _Sent(plan, due)
            mine.append(s)
            late.append(time.perf_counter() - due)
            try:
                rid = eng.add_request(plan.prompt,
                                      max_new_tokens=plan.output_tokens)
            except Exception as e:      # refused or raised: a failed request
                s.error = f"{type(e).__name__}: {e}"
                return
            s.req = eng.results[rid]
            self.sent[rid] = s

        while True:
            now = time.perf_counter()
            if now >= t1:
                break
            if tracer is not None:
                tracer.tick(now)
            with jax.profiler.TraceAnnotation("chipbench.admit"):
                if closed:
                    free = clients - len(self.sent)
                    while free > 0:
                        if next_i == len(plans):
                            plans.extend(more())
                        send(plans[next_i], max(now, t0))
                        next_i += 1
                        free -= 1
                else:
                    while next_i < len(plans) \
                            and t0 + plans[next_i].due_s <= now:
                        send(plans[next_i], t0 + plans[next_i].due_s)
                        next_i += 1
            if not eng.pending and eng.num_active == 0:
                nxt = (t0 + plans[next_i].due_s
                       if not closed and next_i < len(plans) else t1)
                time.sleep(max(0.0, min(nxt, t1) - time.perf_counter()))
                continue
            tokens_before = self.step_tokens.value
            t_begin = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.step"):
                finished = eng.step()
            t_end = time.perf_counter()
            live_ctx = 0
            for req in list(finished) + [r for r in eng.slots
                                         if r is not None]:
                s = self.sent.get(req.rid)
                if s is None:
                    continue
                live_ctx += req.ctx
                s.stamps.extend([t_end] * (len(req.out_tokens)
                                           - len(s.stamps)))
                if req.done:
                    eng.pop_result(req.rid)
                    del self.sent[req.rid]
            steps.append({
                "t_begin": t_begin, "t_end": t_end, "index": eng.steps,
                "rows": eng.num_active + len(finished),
                "waiting": len(eng.pending), "live_context": live_ctx,
                "tokens": self.step_tokens.value - tokens_before})
        return {"t0": t0, "t1": t1, "sent": mine, "steps": steps,
                "late": late, "traffic": traffic_spec,
                "not_sent": 0 if closed else len(plans) - next_i}


def _failed(s: _Sent, vocab: int) -> bool:
    if s.error is not None or s.req is None:
        return True
    toks = s.req.out_tokens
    if any(not 0 <= int(t) < vocab for t in toks):
        return True
    return bool(s.req.done) and len(toks) != s.plan.output_tokens


def summarize(phase: Dict, vocab: int, step_ends: List[float]) -> Dict:
    """Everything a window's numbers are made of. A request due in the
    window with no first token at the cut enters the time-to-first-token
    sample with the time it has waited so far."""
    t0, t1 = phase["t0"], phase["t1"]
    ttft, first_steps, records = [], [], []
    ends = np.asarray(step_ends)
    for s in phase["sent"]:
        stamps = [t for t in s.stamps if t < t1]
        first = stamps[0] if stamps else None
        ttft.append(((first if first is not None else t1) - s.due) * 1e3)
        if first is not None:
            first_steps.append(int(np.searchsorted(ends, first, "right")
                                   - np.searchsorted(ends, s.due, "right")))
        records.append({"due": s.due - t0, "prompt": len(s.plan.prompt),
                        "asked": s.plan.output_tokens, "got": len(stamps),
                        "ttft_ms": ttft[-1], "failed": _failed(s, vocab)})
    return {"ttft_ms": ttft, "first_steps": first_steps, "records": records,
            "attempted": len(phase["sent"]),
            "failed": sum(r["failed"] for r in records)}


def window_tokens(all_sent: List[_Sent], t0: float, t1: float):
    """Output tokens stamped inside [t0, t1) and the gaps between
    consecutive tokens of one request whose later token is inside it; over
    every request that was in flight, whenever it arrived."""
    tokens, gaps = 0, []
    for s in all_sent:
        prev = None
        for t in s.stamps:
            if t0 <= t < t1:
                tokens += 1
                if prev is not None:
                    gaps.append((t - prev) * 1e3)
            prev = t
    return tokens, gaps


def report(ses: Session, before: List[Dict], win: Dict):
    """A measured window's notes, end-to-end values and summary. ``before``
    are the phases that ran ahead of it on the same engine: their requests
    may still be answering inside the window."""
    seconds = win["t1"] - win["t0"]
    steps = win["steps"]
    everyone = [s for ph in before + [win] for s in ph["sent"]]
    all_ends = [s["t_end"] for ph in before + [win] for s in ph["steps"]]
    summary = summarize(win, ses.cfg.vocab_size, all_ends)
    tokens, gaps = window_tokens(everyone, win["t0"], win["t1"])
    step_ms = [(s["t_end"] - s["t_begin"]) * 1e3 for s in steps]
    thirds = [[s["waiting"] for s in steps
               if k / 3 <= (s["t_end"] - win["t0"]) / seconds < (k + 1) / 3]
              for k in range(3)]
    ttft = summary["ttft_ms"]
    by_prompt = {}
    for lo, hi in ((0, 256), (256, 1024), (1024, None)):
        vals = [r["ttft_ms"] for r in summary["records"]
                if lo <= r["prompt"] and (hi is None or r["prompt"] < hi)]
        if vals:
            by_prompt[f"{lo}-{hi or 'up'}"] = median(vals)
    spec = win["traffic"]
    notes = {
        "rate_per_s": spec.get("rate_per_s"), "clients": spec.get("clients"),
        "requests_due_in_window": summary["attempted"],
        "failed": summary["failed"], "not_sent": win["not_sent"],
        "ttft_p50_ms": median(ttft) if ttft else None,
        "ttft_mean_ms": float(np.mean(ttft)) if ttft else None,
        "ttft_p90_ms": quantile(ttft, 0.9) if ttft else None,
        "ttft_p50_ms_by_prompt_tokens": by_prompt,
        "itl_p50_ms": median(gaps) if gaps else None,
        "itl_p95_ms": quantile(gaps, 0.95) if gaps else None,
        "itl_samples": len(gaps), "tokens_in_window": tokens,
        "serve_tokens_per_s": tokens / seconds,
        "steps": len(steps),
        "step_ms_p50": median(step_ms) if step_ms else None,
        "rows_mean": (float(np.mean([s["rows"] for s in steps]))
                      if steps else None),
        "waiting_by_third": [float(np.mean(t)) if t else 0.0 for t in thirds],
        "waiting_at_cut": steps[-1]["waiting"] if steps else None,
        "generator_late_p95_ms": (quantile(win["late"], 0.95) * 1e3
                                  if win["late"] else None),
        "preemptions": ses.eng.preempt_count,
    }
    e2e = {"serve_tokens_per_s": tokens / seconds}
    if ttft:
        e2e["ttft_p50_ms"] = median(ttft)
        e2e["ttft_mean_ms"] = float(np.mean(ttft))
    if gaps:
        e2e["itl_p95_ms"] = quantile(gaps, 0.95)
    return notes, e2e, summary


def run(ctx: Context) -> Result:
    ses = Session(ctx)
    cell = ses.cell
    spec = cell["traffic"]
    ramp_s = float(spec.get("ramp_s", 0))
    ctx.emit("check", **ses.check)
    t_ramp = time.perf_counter()
    ramp = ses.phase(spec, ramp_s, phase_seed=2)
    t_window = t_ramp + ramp_s
    ses.parts["ramp"] = ramp_s
    setup_s = t_window - ctx.t_process
    tracer = Slice(ctx, t_window, cell["trace_slice_s"])
    ses.compiles.armed = True
    win = ses.phase(spec, ctx.seconds, phase_seed=3, tracer=tracer,
                    start_at=t_window)
    ses.compiles.armed = False
    tracer.finish()

    notes, e2e, summary = report(ses, [ramp], win)
    traced = [s for s in win["steps"]
              if tracer.covers(s["t_begin"], s["t_end"])]
    ctx.emit("setup", setup_s=setup_s, parts=ses.parts)
    ctx.emit("notes", compiles_in_window=ses.compiles.count,
             traced_steps=len(traced), **notes)
    e2e["setup_s"] = setup_s
    correct = (ses.check["ok"] and ses.compiles.count == 0
               and summary["attempted"] > 0 and notes["tokens_in_window"] > 0)
    return Result(
        correct=bool(correct), attempted=summary["attempted"],
        failed=summary["failed"],
        end_to_end={k: (v, UNITS[k]) for k, v in e2e.items()},
        steps=win["steps"], traced_steps=traced, requests=summary["records"],
        first_steps=summary["first_steps"],
        reduced=tracer.reduce("chipbench.step"), config=ses.sizes, cell=cell,
        device_kind=ctx.device_kind)
