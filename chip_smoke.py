"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py             one TPU chip: kernels, train, serve
    python chip_smoke.py --chips 4   four chips: the hybrid-mesh train step
                                     and its unsharded comparison, only

One process, the normal entry points (``TrainStep``,
``ContinuousBatchingEngine``, ``fleet.init`` + ``fleet.distributed_model``),
the published Llama-2-7B widths of ``LlamaConfig()`` in bfloat16 with only
the depth cut, random weights and tokens from ``--seed``. Every phase prints
one JSON line and raises the moment a check fails; the last line of a run
that passed is ``{"ok": true, "device": {...}}`` and nothing else. Without
a TPU the script exits non-zero before any phase runs.

The phases are plain functions of a ``Sizes`` so that
``tests/test_chip_smoke.py`` runs the same code at ``Sizes.tiny()`` on the
CPU, where the Pallas kernels are interpreted and the on-chip proofs
(``tpu_custom_call`` in the compiled text, no interpret mode) are skipped.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                               LlamaPretrainingCriterion)

# bf16 kernels against their XLA composite: max abs error over the
# composite's max abs value
KERNEL_RTOL = 2e-2


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def _check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized by. ``config`` carries the widths; its depth is
    replaced per phase by ``train_layers`` / ``serve_layers``."""
    config: LlamaConfig
    # train: TrainStep, the same batch every step
    train_layers: int
    train_batch: int
    train_seq: int
    train_steps: int
    first_loss_bounds: Tuple[float, float]
    # four chips: dp x mp = 2 x 2, batch doubled over dp
    mesh_batch: int
    mesh_steps: int
    # serve: ContinuousBatchingEngine
    serve_layers: int
    max_batch: int
    block_size: int
    token_budget: int
    prefill_chunk: int
    n_requests: int
    head_len: int
    prompt_lens: Tuple[int, ...]
    out_lens: Tuple[int, ...]
    # kernels: flash [b, s, h, d]; ragged mix of prefill chunks and decode
    # rows over a pool of `ragged_blocks`; one fused AdamW bucket
    flash_batch: int
    flash_seq: int
    ragged_chunk: int
    ragged_prefill_rows: int
    ragged_decode_rows: int
    ragged_blocks: int
    ragged_table_width: int
    fused_shapes: Tuple[Tuple[int, ...], ...]

    @staticmethod
    def full() -> "Sizes":
        cfg = LlamaConfig(dtype="bfloat16")     # Llama-2-7B widths
        h, f = cfg.hidden_size, cfg.intermediate_size
        return Sizes(
            config=cfg,
            train_layers=2, train_batch=2, train_seq=2048, train_steps=5,
            # ln(32000) = 10.37 plus the initialiser's logit spread
            first_loss_bounds=(9.5, 12.5),
            mesh_batch=4, mesh_steps=3,
            serve_layers=4, max_batch=8, block_size=64, token_budget=512,
            prefill_chunk=256, n_requests=16, head_len=256,
            prompt_lens=(128, 384, 768), out_lens=(16, 48, 96),
            flash_batch=2, flash_seq=2048,
            ragged_chunk=256, ragged_prefill_rows=4, ragged_decode_rows=12,
            ragged_blocks=512,
            ragged_table_width=cfg.max_position_embeddings // 64,
            # one decoder layer's matrices and a norm: 78.6 M elements,
            # a row count that is not a multiple of the 512-row block
            fused_shapes=((h, f), (h, h), (h, h), (h,)))

    @staticmethod
    def tiny() -> "Sizes":
        cfg = LlamaConfig.tiny()
        return Sizes(
            config=cfg,
            train_layers=2, train_batch=2, train_seq=128, train_steps=5,
            first_loss_bounds=(math.log(cfg.vocab_size) - 0.9,
                               math.log(cfg.vocab_size) + 2.1),
            mesh_batch=4, mesh_steps=3,
            serve_layers=2, max_batch=4, block_size=16, token_budget=48,
            prefill_chunk=32, n_requests=8, head_len=16,
            prompt_lens=(4, 12, 24, 36), out_lens=(2, 3, 5, 8),
            flash_batch=1, flash_seq=128,
            ragged_chunk=16, ragged_prefill_rows=2, ragged_decode_rows=3,
            ragged_blocks=32, ragged_table_width=8,
            fused_shapes=((64, 128), (64, 64), (64,)))


def _emit(record: Dict) -> None:
    print(json.dumps(record), flush=True)


def _on_chip() -> bool:
    return jax.default_backend() == "tpu"


def _require_mosaic() -> None:
    """On the chip every Pallas kernel must go through Mosaic: a kernel
    module that would still pick interpret mode is a failure."""
    from paddle_tpu.ops.kernels.pallas import (bcsr_spmm, flash_attention,
                                               fused_optimizer, grouped_gemm)
    for mod in (flash_attention, fused_optimizer, grouped_gemm, bcsr_spmm):
        _check(not mod._interpret(),
               f"{mod.__name__} would run interpret=True on the chip")
    _check(fused_optimizer.default_use_pallas(),
           "fused optimizer would take the composite on the chip")


def _mosaic_calls(compiled_text: str, what: str) -> int:
    """Mosaic kernels in a compiled program's text; on the chip there
    must be some, or a dispatcher gave way to its composite."""
    n = compiled_text.count('"tpu_custom_call"')
    if _on_chip():
        _check(n > 0, f"no tpu_custom_call in {what}: a Pallas kernel "
                      f"gave way to its composite")
    return n


def _normal(rng, shape, dtype, scale: float = 1.0):
    """Standard normals made on the device, keyed from the host `rng`."""
    key = jax.random.key(int(rng.integers(2 ** 31)))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _rel_err(got, want) -> float:
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _mem(device) -> Dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


# -- kernels ------------------------------------------------------------------

def _flash_case(sz: Sizes, rng) -> Dict:
    from paddle_tpu.ops.kernels.nn import scaled_dot_product_attention
    from paddle_tpu.ops.kernels.pallas import flash_attention as fa
    cfg = sz.config
    d = cfg.hidden_size // cfg.num_attention_heads
    dt = jnp.dtype(cfg.dtype)
    qs = (sz.flash_batch, sz.flash_seq, cfg.num_attention_heads, d)
    ks = (sz.flash_batch, sz.flash_seq, cfg.num_key_value_heads, d)
    q, k, v, w = (_normal(rng, s, dt) for s in (qs, ks, ks, qs))
    _check(fa.supported(qs, ks, True), f"flash kernel refuses {qs}")

    def run(attend):
        def weighted(q, k, v, w):
            out = attend(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.jit(jax.value_and_grad(weighted, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v, w)

    (_, out_p), grads_p = run(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True))
    (_, out_x), grads_x = run(
        lambda q, k, v: scaled_dot_product_attention(q, k, v,
                                                     is_causal=True))
    errs = {"out": _rel_err(out_p, out_x)}
    for name, gp, gx in zip(("dq", "dk", "dv"), grads_p, grads_x):
        errs[name] = _rel_err(gp, gx)
    return {"shape": list(qs), "rel_err": errs}


def _ragged_inputs(sz: Sizes, rng):
    """A mix of prefill chunks (some behind earlier context) and decode
    rows over one pool; every visible position fits the first
    ``ctx_blocks`` columns of the block table."""
    cfg = sz.config
    d = cfg.hidden_size // cfg.num_attention_heads
    dt = jnp.dtype(cfg.dtype)
    bs, chunk = sz.block_size, sz.ragged_chunk
    rows = sz.ragged_prefill_rows + sz.ragged_decode_rows
    ctx_blocks = 2 * chunk // bs
    qlen, ctx = [], []
    for r in range(sz.ragged_prefill_rows):
        qlen.append(chunk)
        ctx.append(chunk + (r * chunk // sz.ragged_prefill_rows) // bs * bs)
    for r in range(sz.ragged_decode_rows):
        qlen.append(1)
        ctx.append(int(rng.integers(1, ctx_blocks * bs + 1)))
    _check(rows * ctx_blocks <= sz.ragged_blocks, "ragged pool too small")
    tables = np.zeros((rows, sz.ragged_table_width), np.int32)
    tables[:, :ctx_blocks] = rng.permutation(sz.ragged_blocks)[
        :rows * ctx_blocks].reshape(rows, ctx_blocks)
    cu = np.concatenate([[0], np.cumsum(qlen)]).astype(np.int32)
    tokens = -(-int(cu[-1]) // 8) * 8     # step padding, as the engine pads
    pool = (sz.ragged_blocks, bs, cfg.num_key_value_heads, d)
    q = _normal(rng, (tokens, cfg.num_attention_heads, d), dt)
    k_pool, v_pool = _normal(rng, pool, dt), _normal(rng, pool, dt)
    return (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(ctx, jnp.int32), jnp.asarray(cu), ctx_blocks)


def _ragged_case(sz: Sizes, rng) -> Dict:
    from paddle_tpu.ops.kernels.pallas import quant_common as qc
    from paddle_tpu.ops.kernels.pallas import ragged_paged_attention as rpa
    from paddle_tpu.ops.kernels.serving import _ragged_composite
    q, k_pool, v_pool, tables, ctx, cu, ctx_blocks = _ragged_inputs(sz, rng)
    _check(rpa.supported(q.shape, k_pool.shape),
           f"ragged kernel refuses {q.shape} over {k_pool.shape}")
    k_scale = qc.absmax_scale(k_pool, axis=-1)
    v_scale = qc.absmax_scale(v_pool, axis=-1)
    pools = {
        "bf16": (k_pool, v_pool, {}),
        "int8": (qc.quantize_symmetric(k_pool, k_scale[..., None]),
                 qc.quantize_symmetric(v_pool, v_scale[..., None]),
                 dict(k_scale=k_scale, v_scale=v_scale)),
    }
    kernel = jax.jit(rpa.ragged_paged_attention)
    # the composite gathers [tokens, context, KV, D]: one row at a time
    # and only the table columns that hold visible context
    composite = jax.jit(_ragged_composite)
    cu_h = np.asarray(cu)
    valid = int(cu_h[-1])
    out = {"tokens": valid, "rows": int(tables.shape[0]),
           "pool": list(k_pool.shape), "tables": list(tables.shape),
           "rel_err": {}}
    for name, (kp, vp, scales) in pools.items():
        got = kernel(q, kp, vp, tables, ctx, cu, **scales)
        want = jnp.concatenate([
            composite(q[cu_h[r]:cu_h[r + 1]], kp, vp,
                      tables[r:r + 1, :ctx_blocks], ctx[r:r + 1],
                      jnp.asarray([0, cu_h[r + 1] - cu_h[r]], jnp.int32),
                      **scales)
            for r in range(tables.shape[0])])
        out["rel_err"][name] = _rel_err(got[:valid], want)
    return out


def _fused_case(sz: Sizes, rng) -> Dict:
    from paddle_tpu.ops.kernels.pallas import fused_optimizer as fok
    low = jnp.dtype(sz.config.dtype)
    low_name = None if low == jnp.float32 else str(low)
    cfg = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "decoupled": True}
    plan = fok.plan_buckets(
        "adam", cfg, tuple((s, "float32", str(low), low_name, 0.01)
                           for s in sz.fused_shapes))
    _check(len(plan.buckets) == 1, "fused shapes must share one bucket")

    def f32(shape, scale):
        return _normal(rng, shape, jnp.float32, scale)

    p = [f32(s, 0.02) for s in sz.fused_shapes]
    g = [_normal(rng, s, low, 1e-3) for s in sz.fused_shapes]
    s = [{"m": f32(x.shape, 1e-3), "v": jnp.square(f32(x.shape, 1e-3))}
         for x in p]

    def apply(use_pallas):
        fn = jax.jit(lambda p, g, s: fok.fused_apply(
            plan, p, g, s, lr=1e-3, step=3.0, inv=1.0, coeff=1.0,
            found=0.0, use_pallas=use_pallas, condition=False))
        return fn(p, g, s)

    (np_p, ns_p, low_p), (np_x, ns_x, low_x) = apply(True), apply(False)
    errs = {"param": max(_rel_err(a, b) for a, b in zip(np_p, np_x)),
            "m": max(_rel_err(a["m"], b["m"]) for a, b in zip(ns_p, ns_x)),
            "v": max(_rel_err(a["v"], b["v"]) for a, b in zip(ns_p, ns_x))}
    if low_name is not None:
        errs["low"] = max(_rel_err(a, b) for a, b in zip(low_p, low_x))
    moved = _rel_err(np_x[0], p[0])
    _check(moved > 0.0, "fused AdamW composite did not move the weights")
    b = plan.buckets[0]
    return {"elements": b.total, "rows": b.rows, "block_rows": b.block_rows,
            "rel_err": errs}


def kernels_phase(sz: Sizes, seed: int) -> Dict:
    """Each Pallas kernel of the main path against its own XLA composite
    on the same inputs."""
    t0 = time.perf_counter()
    if _on_chip():
        _require_mosaic()
    rng = np.random.default_rng(seed)
    rec = {"phase": "kernels", "flash_attention": _flash_case(sz, rng),
           "ragged_paged_attention": _ragged_case(sz, rng),
           "fused_adamw": _fused_case(sz, rng)}
    for kernel in ("flash_attention", "ragged_paged_attention",
                   "fused_adamw"):
        for what, err in rec[kernel]["rel_err"].items():
            _check(math.isfinite(err) and err <= KERNEL_RTOL,
                   f"{kernel} {what}: rel err {err} > {KERNEL_RTOL}")
    rec["seconds"] = time.perf_counter() - t0
    return rec


# -- train --------------------------------------------------------------------

def _reduced(sz: Sizes, layers: int) -> Dict:
    return {"num_hidden_layers": [sz.config.num_hidden_layers, layers]}


def _train_setup(sz: Sizes, seed: int, batch: int, wrap=lambda m: m):
    from paddle_tpu.jit.api import TrainStep
    cfg = dataclasses.replace(sz.config, num_hidden_layers=sz.train_layers)
    paddle.seed(seed)
    inner = LlamaForCausalLM(cfg)
    model = wrap(inner)
    crit = LlamaPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, weight_decay=0.01,
                                 parameters=model.parameters())
    train = TrainStep(model, lambda logits, labels: crit(logits, labels),
                      opt)
    ids = Tensor(jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, sz.train_seq)), jnp.int32))
    return cfg, inner, crit, train, ids


def _run_steps(train, model, ids, steps: int):
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = train((ids,), (ids,))
        jax.block_until_ready([p._data for p in model.parameters()])
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss._data))
    return losses, secs


def _check_losses(sz: Sizes, losses: List[float]) -> None:
    _check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    lo, hi = sz.first_loss_bounds
    _check(lo <= losses[0] <= hi,
           f"first loss {losses[0]} outside [{lo}, {hi}]")
    _check(losses[-1] < losses[0],
           f"loss did not fall on a repeated batch: {losses}")


def train_phase(sz: Sizes, seed: int) -> Dict:
    """A few TrainStep steps of Llama + AdamW on one repeated batch."""
    from paddle_tpu.optimizer.optimizer import fused_counters
    t0 = time.perf_counter()
    cfg, model, _, train, ids = _train_setup(sz, seed, sz.train_batch)
    losses, secs = _run_steps(train, model, ids, sz.train_steps)
    _check_losses(sz, losses)
    kernels = _mosaic_calls(
        train.lower((ids,), (ids,)).compile().as_text(), "the TrainStep")
    return {
        "phase": "train", "reduced": _reduced(sz, sz.train_layers),
        "params": sum(int(p._data.size) for p in model.parameters()),
        "batch": [sz.train_batch, sz.train_seq], "dtype": cfg.dtype,
        "losses": losses, "step_seconds": secs,
        "pallas_custom_calls": kernels,
        # TrainStep applies the optimizer's per-parameter rule inside its
        # own program, so the fused route's counters do not move here
        "fused_optimizer": {k: fused_counters[k]
                            for k in ("updates", "fallbacks")},
        "memory": _mem(jax.devices()[0]),
        "seconds": time.perf_counter() - t0,
    }


# -- serve --------------------------------------------------------------------

def _requests(sz: Sizes, seed: int, vocab: int):
    """Prompts of the mix's lengths, every second one behind a shared
    head (what the prefix cache feeds on)."""
    rng = np.random.default_rng(seed)
    head = rng.integers(0, vocab, sz.head_len).tolist()
    reqs = []
    for i in range(sz.n_requests):
        body = rng.integers(
            0, vocab, sz.prompt_lens[i % len(sz.prompt_lens)]).tolist()
        reqs.append(((head + body) if i % 2 else body,
                     sz.out_lens[i % len(sz.out_lens)]))
    return reqs


def _ragged_step_texts(eng) -> List[str]:
    """The engine's step program, compiled for each geometry it runs: a
    ragged attention dispatcher that gave way to the composite shows in
    the text as a program without its Mosaic call."""
    return [eng._program.compiled(n).as_text() for n in eng.geometries]


class _LogitTap:
    """Stands where the engine holds its model and keeps each step's
    logits."""

    def __init__(self, model):
        self._model = model
        self.config = model.config
        self.logits: List = []

    def __call__(self, *args, **kwargs):
        out = self._model(*args, **kwargs)
        self.logits.append(out._data)
        return out


def _geometry_gap(new_engine, prompt, decode_steps: int) -> Dict:
    """One prompt's decode steps in each geometry of the step program: an
    engine as built runs them in its half-width program, one held to the
    full width as every step ran before there were two. The largest
    difference between their logits over the largest logit, held to the
    kernels' tolerance."""
    def decode_logits(eng):
        tap = _LogitTap(eng.model)
        eng.model = tap
        req = eng.results[eng.add_request(prompt,
                                          max_new_tokens=decode_steps + 1)]
        rows, slots = [], set()
        while not req.done:
            decoding = req.slot is not None and req.ctx >= req.target
            eng.step()
            if decoding:        # the only row: its token is packed first
                rows.append(tap.logits[-1][0, 0])
                slots.add(int(tap.logits[-1].shape[1]))
        return jnp.stack(rows), slots, list(req.out_tokens)

    as_built, full_width = new_engine(), new_engine()
    built = as_built.geometries
    full_width.geometries = built[-1:]
    half, half_slots, half_tokens = decode_logits(as_built)
    full, full_slots, full_tokens = decode_logits(full_width)
    _check(len(half) == len(full) == decode_steps,
           f"{len(half)} and {len(full)} decode steps of {decode_steps}")
    _check(half_slots | full_slots == set(built),
           f"decode steps ran {half_slots} and {full_slots} slots")
    err = _rel_err(half, full)
    _check(err <= KERNEL_RTOL,
           f"a decode step's logits differ by {err} between geometries")
    return {"slots": sorted(half_slots | full_slots),
            "decode_steps": decode_steps, "rel_err": err,
            "same_tokens": half_tokens == full_tokens}


def serve_phase(sz: Sizes, seed: int) -> Dict:
    """The ragged continuous-batching engine over a request mix, twice:
    greedy decoding must repeat token for token."""
    from paddle_tpu.models.serving import ContinuousBatchingEngine
    from paddle_tpu.observability import metrics
    t0 = time.perf_counter()
    cfg = dataclasses.replace(sz.config, num_hidden_layers=sz.serve_layers)
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    reqs = _requests(sz, seed, cfg.vocab_size)
    longest = max(len(p) + n for p, n in reqs)
    num_blocks = sz.max_batch * -(-(longest + sz.block_size)
                                  // sz.block_size) + 2
    hits = metrics.registry().get("serving.prefix_cache.hit_blocks")

    def new_engine():
        return ContinuousBatchingEngine(
            model, max_batch=sz.max_batch, num_blocks=num_blocks,
            block_size=sz.block_size, temperature=0.0,
            token_budget=sz.token_budget, prefill_chunk=sz.prefill_chunk)

    def run():
        eng = new_engine()
        rids = [eng.add_request(p, max_new_tokens=n) for p, n in reqs]
        t = time.perf_counter()
        out = eng.run()
        return eng, [out[r] for r in rids], time.perf_counter() - t

    hits0 = hits.value
    eng, first, t_first = run()
    hit_blocks = hits.value - hits0
    _, second, t_second = run()
    for (_, n), toks in zip(reqs, first):
        _check(len(toks) == n, f"request got {len(toks)} of {n} tokens")
        _check(all(0 <= t < cfg.vocab_size for t in toks),
               "token id outside the vocabulary")
    _check(hit_blocks >= 1, "the prefix cache reported no hit")
    _check(first == second, "a second greedy run gave other tokens")
    kernels = [_mosaic_calls(text, "the engine's step program")
               for text in _ragged_step_texts(eng)]
    return {
        "phase": "serve", "reduced": _reduced(sz, sz.serve_layers),
        "requests": len(reqs), "max_batch": sz.max_batch,
        "token_budget": sz.token_budget, "num_blocks": num_blocks,
        "tokens_out": sum(len(t) for t in first), "steps": eng.steps,
        "prefix_cache_hit_blocks": int(hit_blocks),
        "run_seconds": [t_first, t_second],
        "geometries": list(eng.geometries),
        "geometry_logit_gap": _geometry_gap(new_engine, reqs[0][0], 4),
        "pallas_custom_calls": kernels,
        "memory": _mem(jax.devices()[0]),
        "seconds": time.perf_counter() - t0,
    }


# -- four chips ---------------------------------------------------------------

def mesh_phase(sz: Sizes, seed: int) -> Dict:
    """The train phase's model under dp=2 x mp=2 on four devices of one
    process, against the forward loss of the same weights unsharded on
    device 0."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import topology
    from paddle_tpu.distributed.auto_parallel.aot import collective_stats
    t0 = time.perf_counter()
    _check(jax.device_count() >= 4,
           f"the mesh phase needs 4 devices, found {jax.device_count()}")
    fleet = dist.fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    n_dev = jax.device_count()      # devices past four widen dp
    try:
        cfg, model, crit, train, ids = _train_setup(
            sz, seed, sz.mesh_batch, wrap=fleet.distributed_model)
        # host copies: the first step donates the device buffers
        weights = {n: np.asarray(t._data)
                   for n, t in model.state_dict().items()}
        losses, secs = _run_steps(train, model, ids, sz.mesh_steps)
        _check(all(math.isfinite(x) for x in losses),
               f"loss not finite: {losses}")
        text = train.lower((ids,), (ids,)).compile().as_text()
        sharded = [p for p in model.parameters()
                   if "mp" in jax.tree.leaves(tuple(p._data.sharding.spec))]
        _check(sharded, "no weight is sharded over mp")
        for p in sharded:
            a = p._data
            _check(len(a.sharding.device_set) == n_dev,
                   f"a weight lives on {len(a.sharding.device_set)} of "
                   f"{n_dev} devices")
            _check(a.addressable_shards[0].data.size < a.size,
                   "an mp-sharded weight holds the whole array per device")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()]
        if _on_chip():
            _check(all(in_use), f"a chip holds nothing: {in_use}")
        kernels = _mosaic_calls(text, "the sharded TrainStep")
    finally:
        topology.set_hybrid_communicate_group(None)

    plain = LlamaForCausalLM(cfg)
    missing, unexpected = plain.set_state_dict(weights)
    _check(not missing and not unexpected,
           f"state dict mismatch: {missing} {unexpected}")
    with paddle.no_grad():
        ref_loss = crit(plain(ids), ids)._data
    _check(ref_loss.devices() == {jax.devices()[0]},
           f"the comparison ran on {ref_loss.devices()}")
    ref = float(ref_loss)
    rel = abs(ref - losses[0]) / abs(losses[0])
    _check(rel <= 2e-2, f"unsharded loss {ref} vs sharded {losses[0]}")
    return {
        "phase": "mesh", "reduced": _reduced(sz, sz.train_layers),
        "mesh": {"dp": hcg.get_data_parallel_world_size(),
                 "mp": hcg.get_model_parallel_world_size()},
        "batch": [sz.mesh_batch, sz.train_seq],
        "losses": losses, "step_seconds": secs,
        "unsharded_loss": ref, "rel_diff": rel,
        "mp_sharded_weights": len(sharded),
        "bytes_in_use": in_use,
        "collectives": collective_stats(text),
        "pallas_custom_calls": kernels,
        "seconds": time.perf_counter() - t0,
    }


# -- entry --------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from paddle_tpu import native
    from paddle_tpu.jit.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    _emit({"phase": "start", "device": device, "seed": args.seed,
           "jax": jax.__version__, "compile_cache": cache_dir,
           "native_library": native.available()})
    sz = Sizes.full()
    phases = ((mesh_phase,) if args.chips == 4
              else (kernels_phase, train_phase, serve_phase))
    for phase in phases:
        _emit(phase(sz, args.seed))
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
