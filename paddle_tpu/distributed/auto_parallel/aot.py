"""Deviceless topology-AOT planner: compile the full hybrid-parallel train
step for a TPU pod slice WITHOUT the hardware.

Reference counterpart: the static auto-parallel Engine plans and compiles
whole-cluster programs ahead of execution
(python/paddle/distributed/auto_parallel/static/engine.py:991 — the
`_build`/`_plan`/`_parallel` pipeline over a logical cluster spec). The
TPU-native analog is JAX topology AOT: `jax.experimental.topologies`
yields PjRt device descriptions for a named slice (e.g. ``v5p:4x4x4`` =
64 chips), `jax.jit(...).lower(avals_with_shardings).compile()` runs the
real XLA:TPU compiler against that topology, and the compiled artifact
exposes per-chip memory analysis and the SPMD collective schedule — so
multi-chip fit and overlap are CI-checkable with zero chips attached.

Design notes (TPU-first):
- Parameters are constructed under ``LazyGuard`` (zeros placeholders) and
  enter ``lower()`` as ShapeDtypeStructs carrying NamedShardings — nothing
  8B-sized is ever materialized host-side.
- TP follows the Megatron factorization expressed ONLY as shardings
  (mp_layers stance): qkv/gate/up column-sharded on ``mp``, o/down
  row-sharded, embeddings vocab-sharded; GSPMD inserts the
  all-gathers/reduce-scatters. The scan-stacked layer params ([L, ...])
  shift every rule one axis right.
- The optimizer state is abstract (TrainStep._abstract_state), sharded
  like its parameter — the ZeRO-free TP+DP layout.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "topology_mesh", "llama_param_pspecs", "lower_llama_train_step",
    "collective_stats", "projected_throughput", "plan_llama3_8b_v5p64",
]

# v5p single-chip peaks (bf16 dense MXU + HBM3): the roofline the
# projected-throughput estimate is measured against.
V5P_PEAK_FLOPS = 459e12       # bf16 FLOP/s per chip
V5P_HBM_BYTES_PER_S = 2765e9  # HBM bandwidth per chip


def projected_throughput(compiled, global_batch: int, seq: int,
                         peak_flops: float = V5P_PEAK_FLOPS,
                         hbm_bytes_per_s: float = V5P_HBM_BYTES_PER_S
                         ) -> Dict:
    """Roofline step-time estimate from the compiled executable's own
    cost analysis: per-chip FLOPs and HBM traffic of the SPMD program
    vs device peaks. Closes the VERDICT gap of plans that prove FIT
    (live-HBM) but project no THROUGHPUT — the estimate is what the
    hardware allows if the latency-hiding scheduler fully overlaps
    collectives, i.e. an upper bound the live run is measured against."""
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    traffic = float(cost.get("bytes accessed", 0.0))
    t_compute = flops / peak_flops
    t_memory = traffic / hbm_bytes_per_s
    step_s = max(t_compute, t_memory)
    tokens = float(global_batch * seq)
    return {
        "flops_per_chip": flops,
        "hbm_bytes_per_chip": traffic,
        "compute_seconds": round(t_compute, 6),
        "memory_seconds": round(t_memory, 6),
        "bound": "compute" if t_compute >= t_memory else "memory",
        "step_seconds": round(step_s, 6),
        "tokens_per_sec": round(tokens / step_s, 1) if step_s else None,
        # fraction of the projected step the MXUs are busy — the MFU
        # ceiling this layout can reach on this topology
        "mfu_upper_bound": round(t_compute / step_s, 4) if step_s else None,
    }


@functools.lru_cache(maxsize=None)
def _topology_desc(topology: str, platform: str):
    """Memoized PjRt topology description for a named slice.

    Instantiating the deviceless topology client costs seconds per call
    and the result is pure in ``(topology, platform)``, so every plan and
    every test in one process shares a single client."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(topology, platform=platform)


def topology_mesh(topology: str, axis_shape: Dict[str, int],
                  platform: str = "tpu") -> Mesh:
    """Mesh over a named TPU topology, e.g. ``("v5p:4x4x4", {"dp":8,"mp":8})``.

    The axis order puts the LAST axis innermost (ICI-nearest) — tensor
    parallelism belongs there, data parallelism outermost."""
    topo = _topology_desc(topology, platform)
    devs = np.array(topo.devices)
    want = int(np.prod(list(axis_shape.values())))
    if devs.size != want:
        raise ValueError(f"topology {topology} has {devs.size} devices, "
                         f"axes {axis_shape} need {want}")
    return Mesh(devs.reshape(tuple(axis_shape.values())),
                tuple(axis_shape))


# -- TP sharding rules --------------------------------------------------------

# scan-stacked LlamaDecoderLayer parameter order (nn/stack.py LayerStack
# over models/llama.py LlamaDecoderLayer): q, k, v, o, gate, up, down,
# input_layernorm, post_attention_layernorm
_STACKED_LLAMA_SPECS = {
    0: P(None, None, "mp"),   # q_proj  [L, h, h]        column
    1: P(None, None, "mp"),   # k_proj  [L, h, kv]       column
    2: P(None, None, "mp"),   # v_proj  [L, h, kv]       column
    3: P(None, "mp", None),   # o_proj  [L, h, h]        row
    4: P(None, None, "mp"),   # gate    [L, h, ffn]      column
    5: P(None, None, "mp"),   # up      [L, h, ffn]      column
    6: P(None, "mp", None),   # down    [L, ffn, h]      row
    7: P(None, None),         # ln1     [L, h]           replicated
    8: P(None, None),         # ln2     [L, h]           replicated
}

_SUFFIX_SPECS = {
    "q_proj.weight": P(None, "mp"), "k_proj.weight": P(None, "mp"),
    "v_proj.weight": P(None, "mp"), "o_proj.weight": P("mp", None),
    "gate_proj.weight": P(None, "mp"), "up_proj.weight": P(None, "mp"),
    "down_proj.weight": P("mp", None),
    "embed_tokens.weight": P("mp", None),   # vocab-sharded embedding
    "lm_head.weight": P(None, "mp"),        # vocab-sharded output proj
}


def llama_param_pspecs(model) -> Dict[str, P]:
    """name -> PartitionSpec for a Llama model (scan-stacked or unrolled)."""
    specs: Dict[str, P] = {}
    for name, p in model.named_parameters():
        spec = None
        if ".layer_stack.stacked_" in name:
            idx = int(name.rsplit("_", 1)[1])
            spec = _STACKED_LLAMA_SPECS.get(idx)
        else:
            for suf, s in _SUFFIX_SPECS.items():
                if name.endswith(suf):
                    spec = s
                    break
        if spec is None or len(spec) > p.ndim:
            spec = P()          # norms / biases / unknown: replicate
        specs[name] = spec
    return specs


# -- lowering -----------------------------------------------------------------

def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                sharding=NamedSharding(mesh, spec))


def _shard_like_param(aval_tree, pspec, mesh):
    """Optimizer state shards exactly like its parameter (dims align);
    scalar state (step counters, beta powers) replicates."""
    def one(a):
        if a is None:
            return None
        spec = pspec if len(pspec) <= len(a.shape) else P()
        return _sds(a.shape, a.dtype, mesh, spec)
    return jax.tree.map(one, aval_tree,
                        is_leaf=lambda x: x is None
                        or isinstance(x, jax.ShapeDtypeStruct))


def lower_llama_train_step(model, criterion, optimizer, mesh: Mesh,
                           global_batch: int, seq: int,
                           dp_axis: str = "dp", tp_axis: str = "mp",
                           zero1: bool = False):
    """Lower the FULL TrainStep (fwd+bwd+AdamW, donated state) against
    `mesh`'s (possibly detached-topology) devices. Returns
    (lowered, param_count).

    Tracing runs under `tp_shard_context(mesh, tp_axis, dp_axis)`: no
    hybrid topology exists in this deviceless path (TP is expressed only
    as shardings), so the context is how the attention kernel tier knows
    to emit its shard_map'd Pallas entry instead of tripping GSPMD."""
    from ...jit.api import TrainStep
    from ...ops.kernels.pallas.tp_attention import tp_shard_context

    ts = TrainStep(model, criterion, optimizer)
    ts._abstract_state = True
    ts._build()

    params, buffers, frozen = ts._params, ts._buffers, ts._frozen
    opt = optimizer
    name_of = {id(p): n for n, p in model.named_parameters()}
    pspecs = llama_param_pspecs(model)

    dp_size = mesh.shape[dp_axis]

    def state_spec(pspec, shape):
        """Optimizer-state placement: like the param, plus (zero1) the
        ZeRO-1 dp-shard on the first dim not already taken by TP — the
        layout that turns the dp grad all-reduce into
        reduce-scatter + param all-gather."""
        if not zero1:
            return pspec
        taken = list(pspec) + [None] * (len(shape) - len(pspec))
        for d, ax in enumerate(taken):
            if ax is None and shape[d] % dp_size == 0:
                taken[d] = dp_axis
                return P(*taken)
        return pspec

    p_avals, m_avals, s_avals = [], [], []
    for i, p in enumerate(params):
        spec = pspecs.get(name_of.get(id(p), ""), P())
        sspec = state_spec(spec, p._data.shape)
        p_avals.append(_sds(p._data.shape, p._data.dtype, mesh, spec))
        m = opt._masters[i]
        m_avals.append(None if m is None
                       else _sds(m.shape, jnp.float32, mesh, sspec))
        s_avals.append(_shard_like_param(opt._states[i], sspec, mesh))

    repl = P()
    buf_avals = tuple(_sds(b._data.shape, b._data.dtype, mesh, repl)
                      for b in buffers)
    frz_avals = tuple(_sds(f._data.shape, f._data.dtype, mesh, repl)
                      for f in frozen)
    ids_aval = _sds((global_batch, seq), jnp.int32, mesh, P(dp_axis, None))
    key_aval = jax.ShapeDtypeStruct(ts._dev_key.shape, ts._dev_key.dtype,
                                    sharding=NamedSharding(mesh, repl))
    lr_aval = _sds((), jnp.float32, mesh, repl)
    step_aval = _sds((), jnp.int32, mesh, repl)

    tp_ctx = (tp_shard_context(mesh, head_axis=tp_axis, batch_axis=dp_axis)
              if tp_axis in mesh.shape else contextlib.nullcontext())
    with tp_ctx:
        lowered = ts._compiled.lower(
            (), tuple(p_avals), tuple(m_avals), tuple(s_avals), buf_avals,
            frz_avals, key_aval, (ids_aval,), (ids_aval,), lr_aval,
            step_aval)
    n_params = sum(int(np.prod(p._data.shape)) for p in params)
    return lowered, n_params


def collective_stats(hlo_text: str) -> Dict[str, int]:
    """Counts of SPMD collectives + async (overlapped) forms in an HLO
    dump — the evidence that the latency-hiding scheduler fired."""
    keys = ["all-gather", "reduce-scatter", "all-reduce",
            "collective-permute", "all-to-all"]
    # match op applications only — "all-gather(" — so the sync count does
    # not also swallow "all-gather-start("/"-done(" substrings
    out = {k: hlo_text.count(f"{k}(") for k in keys}
    # the TPU backend runs collectives async when they carry the
    # async_collective_name scheduling attribute (the HLO keeps the sync
    # form; the -start/-done split happens in the backend schedule) —
    # this count is the latency-hiding evidence
    out["async_annotated"] = hlo_text.count("async_collective_name=")
    return out


def plan_llama3_8b_v5p64(tp: int = 8, dp: int = 8,
                         batch_per_dp: int = 1, seq: int = 4096,
                         topology: str = "v5p:4x4x4",
                         layers: Optional[int] = None,
                         zero1: bool = False,
                         compile_now: bool = True) -> Dict:
    """AOT-plan the BASELINE north-star job: Llama-3-8B TP8xDP8 on v5p-64.

    Returns compile stats: per-chip HBM bytes (argument/temp/total),
    collective schedule counts, compile wall time. `layers` shrinks depth
    for fast tests; None = the real 32.

    The plan is pure in its arguments plus the environment fingerprint
    (jax/jaxlib/framework versions, flags, mesh epoch), so with a
    persistent exec store attached the whole stats dict is cached on
    disk: a second process's plan build short-circuits here — before
    the topology client (seconds) and the XLA compile (minutes) — and
    is read-bound."""
    import paddle_tpu as paddle
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    from ...jit import exec_store as _exec_store

    plan_key = ("llama3_8b_v5p64", topology, tp, dp, batch_per_dp, seq,
                layers, zero1)
    st = _exec_store.store()
    if st is not None and compile_now:
        cached = st.get_json("aot_plan", plan_key)
        if cached is not None:
            cached["cached"] = True
            try:
                from ...observability import perf as _perf_mod
                _perf_mod.note_projection(
                    f"llama3_8b_v5p64:tp{tp}xdp{dp}", cached["projected"])
            except Exception:
                pass   # /perfz join is advisory; the plan's own output stands
            return cached

    cfg = LlamaConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32 if layers is None else layers,
        num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=seq, rope_theta=500000.0,
        dtype="bfloat16", use_scan_layers=True, recompute=True,
        # the Pallas flash kernel runs per head-shard under a mesh-aware
        # shard_map (ops/kernels/pallas/tp_attention.py): lowering enters
        # tp_shard_context below, heads ride the mp axis (32 q / 8 kv
        # divide tp=8), and the kernel composes with GSPMD instead of
        # aborting the SPMD partitioner — the composite is only the
        # recorded fallback for non-divisible geometries
        use_flash_attention=True)

    mesh = topology_mesh(topology, {"dp": dp, "mp": tp})
    prev_dtype = paddle.get_default_dtype()
    paddle.set_default_dtype("bfloat16")
    try:
        with paddle.LazyGuard():
            model = LlamaForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(prev_dtype)
    crit = LlamaPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 parameters=model.parameters())

    # importing the TP dispatcher registers its counters (get-or-create
    # semantics keep this idempotent). Counter.inc is gated on
    # FLAGS_metrics, so the flag is forced on for the duration of the
    # trace — the plan's sharded/fallback evidence must not read 0/0
    # just because observability was switched off.
    from ... import flags as _flags
    from ...observability import metrics as _obs
    from ...ops.kernels.pallas import tp_attention as _tpa  # noqa: F401
    m_sharded = _obs.registry().counter("tp_attention.sharded")
    m_fallback = _obs.registry().counter("tp_attention.fallback")
    s0, f0 = m_sharded.value, m_fallback.value
    prev_metrics = _flags.get_flag("metrics")
    if not prev_metrics:
        _flags.set_flags({"metrics": True})

    t0 = time.perf_counter()
    try:
        lowered, n_params = lower_llama_train_step(
            model, lambda logits, labels: crit(logits, labels), opt, mesh,
            global_batch=batch_per_dp * dp, seq=seq, zero1=zero1)
    finally:
        if not prev_metrics:
            _flags.set_flags({"metrics": False})
    lower_s = time.perf_counter() - t0
    out = {"params": n_params, "mesh": {"dp": dp, "mp": tp},
           "topology": topology, "seq": seq, "zero1": zero1,
           "global_batch": batch_per_dp * dp,
           "lower_seconds": round(lower_s, 1),
           # how attention lowered: sharded = shard_map'd Pallas
           # dispatches during this trace, fallback = recorded composite
           # fallbacks (0/nonzero would mean a guard tripped)
           "attention": {"sharded": m_sharded.value - s0,
                         "fallback": m_fallback.value - f0}}
    if not compile_now:
        out["lowered"] = lowered
        return out

    t0 = time.perf_counter()
    compiled = lowered.compile()
    out["compile_seconds"] = round(time.perf_counter() - t0, 1)
    ma = compiled.memory_analysis()
    out["per_chip_bytes"] = {
        "arguments": int(ma.argument_size_in_bytes),
        "outputs": int(ma.output_size_in_bytes),
        "temp": int(ma.temp_size_in_bytes),
        "alias": int(ma.alias_size_in_bytes),
        # donation aliases outputs onto arguments: live = args + temp
        "live": int(ma.argument_size_in_bytes + ma.temp_size_in_bytes),
    }
    hlo = compiled.as_text()
    out["collectives"] = collective_stats(hlo)
    # evidence the flash kernel actually lowered as Mosaic custom calls
    # (0 would mean the shard_map'd Pallas path silently fell back)
    out["pallas_custom_calls"] = hlo.count("tpu_custom_call")
    # roofline projection alongside the live-HBM fit evidence; also
    # registered with the perf plane so /perfz can put the live achieved
    # numbers next to what this plan said the hardware allows
    out["projected"] = projected_throughput(
        compiled, global_batch=batch_per_dp * dp, seq=seq)
    try:
        from ...observability import perf as _perf_mod
        _perf_mod.note_projection(
            f"llama3_8b_v5p64:tp{tp}xdp{dp}", out["projected"])
    except Exception:
        pass   # /perfz join is advisory; the plan's own output stands
    if st is not None:
        _persist_plan(st, plan_key, out, compiled, topology)
    return out


def _persist_plan(st, plan_key, out, compiled, topology) -> None:
    """Commit the plan stats dict, and best-effort the compiled SPMD
    artifact + serialized topology description alongside it (deviceless
    executables and some backends refuse serialization: fail open, the
    stats dict alone already makes the second process read-bound)."""
    st.put_json("aot_plan", plan_key, out)
    try:
        from jax.experimental import serialize_executable as _se
        import pickle as _pickle
        payload = _pickle.dumps(_se.serialize(compiled))
    except Exception:
        payload = None
    if payload is not None:
        st.put("aot_exec", plan_key, payload, topology=topology)
    try:
        blob = _topology_desc(topology, "tpu").serialize()
    except Exception:
        blob = None
    if blob is not None:
        st.put("topology", (topology,), bytes(blob))
