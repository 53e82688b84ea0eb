"""Serving cells: ``ContinuousBatchingEngine`` under open-loop or
closed-loop traffic, one process, one thread.

The loop is the server a user would write around the engine: hand over the
requests that are due, call ``engine.step()``, read which rows got a token.
Every time is the host's clock after a step has returned, which is after the
sampled tokens have come back from the device. A request's clock starts when
it was due, not when the loop got round to adding it.
"""

from __future__ import annotations

import gc
import time
from statistics import median
from typing import Dict, List, Optional

import numpy as np

from .. import traffic
from .common import (CompileCounter, Context, Heartbeat, Result, Slice,
                     family, memory_peak_bytes, model_sizes, quantile, sized)

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
        self.family = family(config)
        self.model, self.cfg, self.weights = self.family.build_model(
            self.sizes, ctx.seed)
        self.model.eval()
        jax.block_until_ready(list(self.weights.values()))
        self.parts["weights"] = time.perf_counter() - t
        t = time.perf_counter()
        self.eng = ContinuousBatchingEngine(self.model, **config["engine"])
        self.step_tokens = metrics.registry().get("serving.step_tokens")
        self.compiles = CompileCounter()
        self.sent: Dict[int, _Sent] = {}       # rid -> request in flight
        self.warm = self._warm_up()
        self.parts["engine_warmup"] = time.perf_counter() - t

    # -- correctness, outside any window -------------------------------------
    def _warm_up(self) -> Dict:
        """One seeded prompt through the engine's own path: chunked prefill,
        then decode steps through the paged cache. It warms both geometries
        up, and every position's logits are kept for ``judge``."""
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
        return {"prompt": prompt, "out": list(req.out_tokens),
                "got": jnp.concatenate(rows)}

    def release_engine(self) -> None:
        """The window is closed and the peak is read: the engine and its
        pools go, and the reference has the chip."""
        self.eng = self.model = None
        gc.collect()

    def judge(self) -> Dict:
        """The warm-up's logits against the reference's full forward over
        the same tokens. Run once the engine is released: the reference's
        float32 copies would otherwise set the process's peak memory."""
        import jax.numpy as jnp
        spec = self.cell["check"]
        prompt, out, got = (self.warm[k] for k in ("prompt", "out", "got"))
        ids = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        want = self.family.logits(self.sizes, self.weights, ids,
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
    """Output tokens stamped inside [t0, t1), the gaps between consecutive
    tokens of one request whose later token is inside it, and for each gap
    the stamp of that later token (the end of the step that made it); over
    every request that was in flight, whenever it arrived."""
    tokens, gaps, gap_stamps = 0, [], []
    for s in all_sent:
        prev = None
        for t in s.stamps:
            if t0 <= t < t1:
                tokens += 1
                if prev is not None:
                    gaps.append((t - prev) * 1e3)
                    gap_stamps.append(t)
            prev = t
    return tokens, gaps, gap_stamps


def over_half_budget_share(gap_stamps: List[float], steps: List[Dict],
                           smallest_geometry: int) -> Optional[float]:
    """Share (%) of the gap samples whose later token came from a step that
    packed more tokens than the engine's smallest geometry holds, so ran a
    larger program. It says on which side of its two step times the 95th
    percentile of the gaps sits: near 5 % it swings with the seed."""
    if not gap_stamps:
        return None
    packed = {s["t_end"]: s["tokens"] for s in steps}
    over = sum(packed[t] > smallest_geometry for t in gap_stamps)
    return 100.0 * over / len(gap_stamps)


def served_sample(phases: List[Dict], t0: float, t1: float, n: int,
                  rng: np.random.Generator) -> List[_Sent]:
    """``n`` of the requests that finished inside [t0, t1): the longest
    (prompt and answer), and the others drawn by ``rng``."""
    done = [s for ph in phases for s in ph["sent"]
            if s.error is None and s.req is not None and s.req.done
            and s.stamps and t0 <= s.stamps[-1] < t1]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.plan.prompt)
                  + len(s.req.out_tokens))
    rest = [s for s in done if s is not longest]
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _widest_gap(want, tokens, n):
    """Over the first ``n`` rows of ``want`` [rows, vocab]: the widest
    distance of ``tokens``' logits below the row's best, the largest
    |logit|, and how many of the tokens are the row's best."""
    import jax.numpy as jnp
    live = jnp.arange(want.shape[0]) < n
    best = jnp.max(want, -1)
    theirs = jnp.take_along_axis(want, tokens[:, None], -1)[:, 0]
    return (jnp.max(jnp.where(live, best - theirs, 0.0)),
            jnp.max(jnp.where(live[:, None], jnp.abs(want), 0.0)),
            jnp.sum(live & (theirs == best)))


def served_check(fam, sizes: Dict, weights: Dict, sample: List[_Sent],
                 spec: Dict, answer_rows: int, control: bool = False) -> Dict:
    """The window's own answers against the plain reference. For each
    sampled request the reference runs once over the prompt and the tokens
    that were served, and every served token's logit is held against the
    reference's best at its position: ``served_gap`` is the widest distance
    below it, over the largest |reference logit| of the sample. A greedy
    engine that rounds as the configuration says stays within its rounding
    of the best; a token from a coarser path, or one altered after it was
    sampled, lies further down. With ``control`` the same positions are
    also run through the reference at each of ``control_precisions`` and
    the gap of the token that it puts first is read. Every request runs at
    one shape, ``pad_tokens`` positions and ``answer_rows`` logit rows (the
    traffic's longest answer), so the reference compiles once."""
    import jax
    import jax.numpy as jnp
    widest = jax.jit(_widest_gap)
    gap_max, top, tokens, agree = 0.0, 0.0, 0, 0
    controls = {p: 0.0 for p in spec.get("control_precisions", [])} \
        if control else {}
    for s in sample:
        out = np.asarray(s.req.out_tokens, np.int32)
        lo = len(s.plan.prompt) - 1
        ids = np.concatenate([s.plan.prompt, out[:-1]]).astype(np.int32)
        # causal: zeros after the last position change nothing before it
        need = max(len(ids), lo + answer_rows)
        ids = np.pad(ids, (0, need + -need % spec["pad_tokens"] - len(ids)))
        rows = (lo, answer_rows)
        want = fam.logits(sizes, weights, ids, spec["query_block"], rows=rows)
        served = jnp.asarray(np.pad(out, (0, answer_rows - len(out))))
        gap, biggest, same = widest(want, served, len(out))
        gap_max, top = max(gap_max, float(gap)), max(top, float(biggest))
        tokens += len(out)
        agree += int(same)
        for p in controls:
            low = fam.logits(sizes, weights, ids, spec["query_block"],
                             rows=rows, precision=p)
            gap, _, _ = widest(want, jnp.argmax(low, -1), len(out))
            controls[p] = max(controls[p], float(gap))
    # nothing finished, nothing compared: the widest a gap can be, two of
    # the largest |logit|, so the run is not correct and the line stays JSON
    gap = gap_max / top if top > 0 else 2.0
    found = {"ok": bool(sample) and gap <= spec["served_gap_tol"],
             "served_gap": gap, "served_gap_tol": spec["served_gap_tol"],
             "requests": len(sample), "served_tokens": tokens,
             "served_is_reference_best_share": agree / max(tokens, 1),
             "longest_tokens": max((len(s.plan.prompt)
                                    + len(s.req.out_tokens)
                                    for s in sample), default=0),
             "ref_logit_max": top}
    for p, g in controls.items():
        found[f"control_gap_{p}"] = g / top if top > 0 else 2.0
    return found


def report(ses: Session, before: List[Dict], win: Dict):
    """A measured window's notes, end-to-end values and summary. ``before``
    are the phases that ran ahead of it on the same engine: their requests
    may still be answering inside the window."""
    seconds = win["t1"] - win["t0"]
    steps = win["steps"]
    everyone = [s for ph in before + [win] for s in ph["sent"]]
    all_steps = [s for ph in before + [win] for s in ph["steps"]]
    all_ends = [s["t_end"] for s in all_steps]
    summary = summarize(win, ses.cfg.vocab_size, all_ends)
    tokens, gaps, gap_stamps = window_tokens(everyone, win["t0"], win["t1"])
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
        "itl_p99_ms": quantile(gaps, 0.99) if gaps else None,
        "itl_over_half_budget_share": over_half_budget_share(
            gap_stamps, all_steps, ses.eng.geometries[0]),
        "itl_samples": len(gaps), "tokens_in_window": tokens,
        "serve_tokens_per_s": tokens / seconds,
        "steps": len(steps),
        "steps_over_half_budget": sum(
            s["tokens"] > ses.eng.geometries[0] for s in steps),
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
    t_ramp = time.perf_counter()
    heart = Heartbeat().start(ctx.heartbeat)
    ramp = ses.phase(spec, ramp_s, phase_seed=2)
    ramp_stops = heart.stop()
    t_window = t_ramp + ramp_s
    ses.parts["ramp"] = ramp_s
    setup_s = t_window - ctx.t_process
    tracer = Slice(ctx, t_window, cell["trace_slice_s"])
    ses.compiles.armed = True
    heart = Heartbeat().start(ctx.heartbeat)
    win = ses.phase(spec, ctx.seconds, phase_seed=3, tracer=tracer,
                    start_at=t_window)
    stops = heart.stop()
    ses.compiles.armed = False
    tracer.finish()

    notes, e2e, summary = report(ses, [ramp], win)
    traced = [s for s in win["steps"]
              if tracer.covers(s["t_begin"], s["t_end"])]
    ctx.emit("setup", setup_s=setup_s, parts=ses.parts)
    # a stop in the ramp leaves a backlog that the window then works off:
    # its longest says whether the window began in the steady state
    ctx.emit("notes", compiles_in_window=ses.compiles.count,
             traced_steps=len(traced), **notes, **stops,
             ramp_stops_over_50ms=ramp_stops["stops_over_50ms"],
             ramp_stop_longest_ms=ramp_stops["stop_longest_ms"],
             waiting_at_start=(win["steps"][0]["waiting"]
                               if win["steps"] else None))
    e2e["setup_s"] = setup_s
    reduced = tracer.reduce("chipbench.step")
    peak = memory_peak_bytes(cell["chips"])
    ses.release_engine()
    check = ses.judge()
    ctx.emit("check", **check)
    compared = {"logit_gap": [check["logit_gap"], check["logit_gap_tol"]],
                "compiles_in_window": [ses.compiles.count, 0]}
    correct = (check["ok"] and ses.compiles.count == 0
               and summary["attempted"] > 0 and notes["tokens_in_window"] > 0)
    if "served" in cell["check"]:
        sample = served_sample(
            [ramp, win], win["t0"], win["t1"], cell["check"]["served"][
                "requests"], np.random.default_rng([ctx.seed, 4]))
        t = time.perf_counter()
        answers = spec["output_tokens"]
        served = served_check(ses.family, ses.sizes, ses.weights, sample,
                              cell["check"]["served"],
                              int(answers.get("max") or answers["value"]),
                              ctx.control)
        ctx.emit("served_check", seconds=time.perf_counter() - t, **served)
        compared["served_gap"] = [served["served_gap"],
                                  served["served_gap_tol"]]
        correct = correct and served["ok"]
    return Result(
        correct=bool(correct), attempted=summary["attempted"],
        failed=summary["failed"],
        end_to_end={k: (v, UNITS[k]) for k, v in e2e.items()},
        steps=win["steps"], traced_steps=traced, requests=summary["records"],
        first_steps=summary["first_steps"], reduced=reduced,
        config=ses.sizes, cell=cell, device_kind=ctx.device_kind,
        compared=compared, memory_peak_bytes=peak)
