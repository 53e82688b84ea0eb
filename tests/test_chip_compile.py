"""Compile-only checks of the main path's Pallas kernels for a described
TPU v5e (no chip attached): the real XLA:TPU + Mosaic compilers at the
published Llama-2-7B widths chip_smoke.py runs, ~2 s each.

Interpret mode cannot show what these do: a slice off the tiling, too much
VMEM, a scalar-memory table Mosaic refuses. Nothing runs, so no result and
no time comes from here.

The topology is described inside the module-scoped fixture and nowhere
else: only one process may hold libtpu, every xdist worker imports this
file, and only the worker that is handed it may load the library. Keep
these tests in this one file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from paddle_tpu.ops.kernels.pallas import flash_attention as fa
from paddle_tpu.ops.kernels.pallas import fused_optimizer as fok
from paddle_tpu.ops.kernels.pallas import ragged_paged_attention as rpa

# Llama-2-7B (LlamaConfig()): 32 heads, 32 kv heads, head_dim 128
H, KV, D = 32, 32, 128
HIDDEN, FFN = 4096, 11008
CUSTOM_CALL = '"tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, no_persistent_cache):
    """Interpret mode off, as on the chip: the host backend here is the
    CPU, which the kernels' `_interpret()` would otherwise follow."""
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", False)
    monkeypatch.setattr(fok, "_interpret", lambda: False)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


_TEXTS = {}


def _compiled(case, build):
    """The compiled text of ``case``, compiled once a process: the cases
    that read the kernels' names share it with the cases that compile."""
    if case not in _TEXTS:
        _TEXTS[case] = build()
    return _TEXTS[case]


def _flash_text(one_chip, kv_heads):
    q = _sds(one_chip, (2, 2048, H, D), jnp.bfloat16)
    kv = _sds(one_chip, (2, 2048, kv_heads, D), jnp.bfloat16)
    assert fa.supported(q.shape, kv.shape, True)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    return _compiled(("flash", kv_heads), lambda: jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile().as_text())


@pytest.mark.parametrize("kv_heads", [32, 8], ids=["mha", "gqa"])
def test_flash_fwd_bwd(mosaic, one_chip, kv_heads):
    text = _flash_text(one_chip, kv_heads)
    assert text.count(CUSTOM_CALL) >= 3      # fwd, dq, dk/dv


def test_sharded_flash_on_the_hybrid_mesh(mosaic, topo):
    # fleet.init's mesh always has its five axes, here dp=2 x mp=2 with
    # three of degree 1. Mosaic takes a kernel only in a region that is
    # manual over every one of them (found on four chips, PR 22)
    from paddle_tpu.ops.kernels.pallas import tp_attention as tpa
    mesh = Mesh(np.array(topo.devices).reshape(2, 1, 1, 1, 2),
                ("dp", "pp", "sharding", "sep", "mp"))
    qkv = jax.ShapeDtypeStruct(
        (4, 2048, H, D), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "mp", None)))

    def loss(q, k, v):
        out = tpa.sharded_flash_attention(q, k, v, mesh, "mp", "dp",
                                          causal=True)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv).compile().as_text()
    assert text.count(CUSTOM_CALL) >= 3


# the ragged step's shapes: (q heads, kv heads, packed tokens, rows, pool
# blocks, table width), 64-token blocks, head_dim 128
RAGGED_SHAPES = {
    # chip_smoke's serve phase: Llama-2-7B, 8 rows, tables as wide as
    # max_position_embeddings / block_size. 32 KV heads: the ring's largest
    # slots, 1 MiB a visit in bf16 (ISSUE 38)
    "llama2-7b": (H, KV, 512, 8, 130, 64),
    # chipbench's serve-chat-steady and serve-decode-batch: Mistral-7B-v0.3,
    # 64 rows, the 8.6 GB pool, 32768 / 64 = 512 table columns in scalar
    # memory; the budget's geometry and the half-width one
    "mistral-7b-cell": (32, 8, 512, 64, 4096, 512),
    "mistral-7b-cell-half": (32, 8, 256, 64, 4096, 512),
    # ISSUE 40: the packed rows and the packed output are resident in VMEM
    # beside the kernel's own limit, so they bound a step's tokens. The
    # most `supported()` takes at these widths: 24 + 24 MiB
    "mistral-7b-most-tokens": (32, 8, 1536, 64, 4096, 512),
    # chipbench's serve-reason-batch: AI21-Jamba2-3B's attention layers, 20
    # query heads on 1 KV head pooled in float32, 256 rows
    "jamba2-3b-cell": (20, 1, 512, 256, 8448, 32),
    "jamba2-3b-cell-half": (20, 1, 256, 256, 8448, 32),
}
RAGGED_CASES = [
    (shapes, dtype)
    for shapes in sorted(RAGGED_SHAPES)
    for dtype in ((jnp.float32,) if shapes.startswith("jamba")
                  else (jnp.bfloat16, jnp.int8))]


def _ragged_args(shapes, pool_dtype, sharding):
    heads, kv_heads, tokens, rows, blocks, width = RAGGED_SHAPES[shapes]
    bs = 64

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding(spec))

    q = sds((tokens, heads, D), jnp.bfloat16, P(None, "mp", None))
    pool = sds((blocks, bs, kv_heads, D), pool_dtype,
               P(None, None, "mp", None))
    assert rpa.supported(q.shape, pool.shape)
    scales = {}
    if pool_dtype == jnp.int8:
        s = sds((blocks, bs, kv_heads), jnp.float32, P(None, None, "mp"))
        scales = dict(k_scale=s, v_scale=s)
    return (q, pool, pool, sds((rows, width), jnp.int32),
            sds((rows,), jnp.int32), sds((rows + 1,), jnp.int32)), scales


def _ragged_text(one_chip, pool_dtype, shapes="llama2-7b"):
    args, scales = _ragged_args(shapes, pool_dtype, lambda spec: one_chip)
    return _compiled(("ragged", shapes, pool_dtype), lambda: jax.jit(
        rpa.ragged_paged_attention).lower(*args, **scales)
        .compile().as_text())


@pytest.mark.parametrize(
    "shapes, pool_dtype", RAGGED_CASES,
    ids=[f"{s}-{jnp.dtype(d).name}" for s, d in RAGGED_CASES])
def test_ragged_paged_attention(mosaic, one_chip, shapes, pool_dtype):
    # the kv loop's dynamic trip count, the pool left in HBM and the block
    # DMAs out of it, the table in scalar memory: interpret mode takes all
    # of them, Mosaic has to (ISSUE 27). ISSUE 38: the ring of VMEM slots
    # that outlives a grid step, its cursor in scalar memory, the q and
    # output blocks indexed through the count of live tiles, and the rings
    # of every pool within the kernel's stated VMEM limit
    assert CUSTOM_CALL in _ragged_text(one_chip, pool_dtype, shapes)


def _ragged_bodies(shapes, pool_dtype):
    """The query rows of each body the kernel keeps state for at ``shapes``:
    its scratch after the fetch cursor, (q, m, l, acc) a body."""
    args, scales = _ragged_args(shapes, pool_dtype, lambda spec: None)
    jaxpr = jax.make_jaxpr(
        lambda *a, **k: rpa.ragged_paged_attention(*a, **k))(*args, **scales)
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    n = call.params["grid_mapping"].num_scratch_operands
    scratch = [v.aval for v in call.params["jaxpr"].invars[-n:]]
    cursor = next(i for i, a in enumerate(scratch)
                  if a.shape == (2,) and a.dtype == jnp.int32)
    return [a.shape[1] for a in scratch[cursor + 1::4]]


@pytest.mark.parametrize("shapes, pool_dtype, rows", [
    ("jamba2-3b-cell", jnp.float32, [160, 24]),
    ("jamba2-3b-cell-half", jnp.float32, [160, 24]),
    ("mistral-7b-cell", jnp.bfloat16, [32, 8]),
    ("mistral-7b-cell", jnp.int8, [32, 8]),
    ("llama2-7b", jnp.bfloat16, [8]),
], ids=["jamba-f32", "jamba-half-f32", "mistral-bf16", "mistral-int8",
        "llama2-mha-bf16"])
def test_ragged_one_token_body(mosaic, one_chip, shapes, pool_dtype, rows):
    # a tile of one valid token runs a body on G heads a KV head
    # rounded up to 8 rows (24 at Jamba's 20 on 1, 8 at Mistral's 4 on 1),
    # beside the full body's TQ * G; Mosaic takes both, the load and store
    # of the packed row as one reshape, and the visits two at a time. At
    # G = 1 the full body is 8 rows already and is the only one compiled.
    # The step programs of both cells compile this kernel in both
    # geometries (test_serving_step_owns_its_pools,
    # test_jamba_step_owns_its_pools_and_row_state)
    assert CUSTOM_CALL in _ragged_text(one_chip, pool_dtype, shapes)
    assert _ragged_bodies(shapes, pool_dtype) == rows


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_sharded_ragged_on_the_hybrid_mesh(mosaic, topo, pool_dtype):
    # heads and the pool's kv heads over mp, rows replicated over dp: the
    # kernel sees 16 heads over 4 kv heads and DMAs from its pool shard
    from paddle_tpu.ops.kernels.pallas import tp_attention as tpa
    mesh = Mesh(np.array(topo.devices).reshape(2, 1, 1, 1, 2),
                ("dp", "pp", "sharding", "sep", "mp"))
    args, scales = _ragged_args("mistral-7b-cell", pool_dtype,
                                lambda spec: NamedSharding(mesh, spec))

    def attend(q, kp, vp, tbl, lens, cu, **scales):
        out = tpa.sharded_ragged_paged_attention(
            q, kp, vp, tbl, lens, cu, mesh, "mp", **scales)
        assert out is not None
        return out

    text = jax.jit(attend).lower(*args, **scales).compile().as_text()
    assert _custom_call_names(text) == {"ragged_paged_attention"}


def _step_texts(one_chip, make_model, rows, blocks, width, budget=512):
    """The engine's step program of ``make_model()`` in both geometries of
    a ``budget``-token budget (slots -> compiled text), lowered from shapes:
    ``rows`` rows, pools of ``blocks`` blocks of 64, tables ``width`` wide.
    The parameters stay the zeros `LazyGuard` puts in host memory, never
    initialized. Returns (texts, parameters and buffers, pool arrays)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.api import _collect_state
    from paddle_tpu.models.generation import PagedKVCache, layer_states
    from paddle_tpu.models.serving import _StepProgram, _geometries

    geometries = _geometries(budget, rows, 0)
    assert geometries == (budget // 2, budget)
    with paddle.LazyGuard():
        model = make_model()
    model.eval()
    for _, sub, _ in model._walk(""):
        sub.__dict__.pop("_has_lazy", None)
        for p in sub._parameters.values():
            if p is not None and hasattr(p, "_lazy_spec"):
                del p._lazy_spec
    # the cache gives the program its geometry; the pools (and the
    # row-state arrays) it is lowered for are the cell's, as shapes
    layers = layer_states(model)
    cache = PagedKVCache(len(layers), 1, num_blocks=2, block_size=64,
                         max_blocks_per_seq=width, dtype="bfloat16",
                         layers=layers)
    params, buffers = _collect_state(model)
    state = tuple(_sds(one_chip, t._data.shape, t._data.dtype)
                  for t in params + buffers)
    paged = sum(len(l) for l in cache.paged_lists())
    pools = tuple(
        _sds(one_chip, ((blocks,) if i < paged else (rows + 1,))
             + a.shape[1:], a.dtype)
        for i, a in enumerate(cache.pools()))

    def i32(*shape):
        return _sds(one_chip, shape, jnp.int32)

    program = _StepProgram(cache, geometries)
    texts = {n: program.lower(model, (
        state, pools, i32(1, n), i32(1, n), i32(n),
        i32(rows, width), i32(rows), i32(rows + 1),
        i32(rows), i32(n))).compile().as_text()    # prev, src (ISSUE 35)
        for n in geometries}
    return texts, len(state), len(pools)


def _aliased(text):
    """The parameter numbers that the compiled text aliases to outputs."""
    head = text[:text.index("\n")]
    alias = head[head.index("input_output_alias={"):
                 head.index("entry_computation_layout")]
    return {int(m) for m in re.findall(r"\((\d+), \{\}", alias)}


def _pool_parameters(text):
    """The numbers of the compiled entry computation's parameters that are
    the step's pools (the program's second argument). Not the pools' place
    among the arguments: an argument the program never reads has no
    parameter, and a step reads the rope tables of its first layer only."""
    entry = text[text.index("\nENTRY "):]
    names = re.findall(r"[(,] ?(\w+)\.\d+: ", entry[:entry.index("\n", 1)])
    return {i for i, name in enumerate(names) if name.startswith("pools_")}


def _serving_step_text(one_chip, layers=8, budget=512):
    """The engine's step program at chipbench's `mistral-7b-v0.3-serve`:
    Mistral-7B-v0.3 widths, 8 layers, 64 rows and 16 bf16 pools of 4096
    blocks (4 GB of parameters in host memory)."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    def make():
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=32768, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=layers, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=32768,
            rms_norm_eps=1e-5, rope_theta=1e6, dtype="bfloat16"))

    return _compiled(("serving_step", layers, budget), lambda: _step_texts(
        one_chip, make, rows=64, blocks=4096, width=512, budget=budget))


@pytest.mark.parametrize("slots", [512, 256], ids=["budget", "half"])
def test_serving_step_owns_its_pools(mosaic, one_chip, slots):
    # ISSUE 30: one program for the ragged step's model call. Every pool is
    # an argument aliased to an output, so the slots are written in place;
    # the per-op path copied a 537 MB pool for each of 16 writes. ISSUE 32:
    # the same holds for the half-width program of a step that packs at
    # most 256 tokens
    texts, _, n_pools = _serving_step_text(one_chip)
    text = texts[slots]
    assert n_pools == 16
    assert len(_pool_parameters(text)) == n_pools
    assert _aliased(text) == _pool_parameters(text)
    assert not re.search(r"= bf16\[4096,64,8,128\]\S* copy\(", text)
    assert _custom_call_names(text) == {"ragged_paged_attention"}
    assert text.count(CUSTOM_CALL) >= 8          # one call a layer


@pytest.mark.parametrize("slots", [1536, 768], ids=["budget", "half"])
def test_serving_step_at_the_most_tokens_the_kernel_takes(mosaic, one_chip,
                                                          slots):
    # ISSUE 40: the packed rows and the packed output are whole in VMEM for
    # the call, XLA's allocation beside the kernel's own limit, and in the
    # step program XLA holds the output, that limit and 2.5 MiB to 64 MiB.
    # `supported()` takes the kernel up to 1536 tokens at these widths (a
    # step of 1920 does not compile), and the composite past them
    assert rpa.supported((1536, 32, D), (4096, 64, 8, D))
    assert not rpa.supported((1537, 32, D), (4096, 64, 8, D))
    texts, _, _ = _serving_step_text(one_chip, layers=2, budget=1536)
    assert _custom_call_names(texts[slots]) == {"ragged_paged_attention"}
    assert texts[slots].count(CUSTOM_CALL) == 2


def _jamba_step_text(one_chip):
    """The step program at chipbench's `jamba2-3b-serve`: AI21-Jamba2-3B as
    published, all 28 layers (6 GB of parameters in host memory), 256
    rows, 2 x 2 pools of 8448 blocks and 26 x 2 row-state arrays."""
    from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM

    return _compiled("jamba_step", lambda: _step_texts(
        one_chip, lambda: JambaForCausalLM(JambaConfig(dtype="bfloat16")),
        rows=256, blocks=8448, width=32))


@pytest.mark.parametrize("slots", [512, 256], ids=["budget", "half"])
def test_jamba_step_owns_its_pools_and_row_state(mosaic, one_chip, slots):
    # ISSUE 34: two kinds of state in one program. The 2 attention layers'
    # pools and the 26 Mamba layers' convolution tails and SSM states are
    # all donated and written in place: a copy of one SSM array would move
    # 84 MB, of all of them 2.2 GB a step. Mosaic takes the scan's and the
    # convolution's row walk (state tiles copied in and out of HBM by the
    # kernel) and the ragged kernel at 20 query heads on 1 KV head, whose
    # pool is float32 because a bfloat16 one cannot be copied by the block
    texts, _, n_pools = _jamba_step_text(one_chip)
    text = texts[slots]
    assert n_pools == 2 + 2 + 26 + 26
    assert len(_pool_parameters(text)) == n_pools
    assert _aliased(text) == _pool_parameters(text)
    for shape in (r"f32\[257,16,40,128\]", r"bf16\[257,3,40,128\]",
                  r"f32\[8448,64,1,128\]"):
        assert not re.search(rf"= {shape}\S* copy\(", text), shape
    assert _custom_call_names(text) == {
        "ragged_paged_attention", "ragged_selective_scan",
        "ragged_causal_conv"}
    assert text.count(CUSTOM_CALL) == 2 + 26 + 26


_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "pred": 1}
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%(\S+) = (\w+)\[([\d,]*)\](\{[^}]*\})? ([\w\-]+)\((.*)$")
# what stands between a weight in the program's state and a copy of it: a
# view, or XLA's own prefetch of the array into VMEM
_SEES_THROUGH = {"bitcast", "copy-start", "copy-done", "slice-start",
                 "slice-done", "get-tuple-element", "custom-call"}


def _entry_instructions(text):
    """name -> (dtype, dims, layout, opcode, the rest of its line) for the
    instructions of the compiled text's entry computation."""
    entry = text[text.index("\nENTRY "):]
    found = {}
    for line in entry.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            dims = tuple(int(d) for d in m.group(3).split(",") if d)
            found[m.group(1)] = (m.group(2), dims, m.group(4) or "",
                                 m.group(5), m.group(6))
    return found


def _reads_state(name, found):
    """Whether instruction ``name`` is a parameter of the program's state
    seen through views and prefetches."""
    while name in found and found[name][3] in _SEES_THROUGH:
        operand = re.search(r"%([^\s,)]+)", found[name][4])
        if operand is None:
            return False
        name = operand.group(1)
    return name.startswith("state_arrays")


def _op_name(rest):
    m = re.search(r'op_name="([^"]*)"', rest)
    return m.group(1) if m else ""


STEP_PROGRAMS = {
    # the step program's texts by slot count; query heads x head_dim
    "mistral": (lambda c: _serving_step_text(c)[0], 4096),
    "jamba": (lambda c: _jamba_step_text(c)[0], 2560),
}


@pytest.mark.parametrize("slots", [512, 256], ids=["budget", "half"])
@pytest.mark.parametrize("program", sorted(STEP_PROGRAMS))
class TestQueriesStayRows:
    """ISSUE 40: from `q_proj` to `o_proj` the queries are [T, H*D] rows and
    nothing reorders their axes. A consumer that wants another order (a
    [T, H, D] view for rope's tables, a tile pack's transposition) has XLA
    write `q_proj`'s product tokens-minor and lay the WEIGHT out again every
    step to get there: 33.5 MB read and written a layer. Properties of the
    compiled text, so a later change that brings a transposition back fails
    here, on the CPU."""

    def test_no_relayout_around_the_ragged_kernel(self, mosaic, one_chip,
                                                  program, slots):
        text_of, _ = STEP_PROGRAMS[program]
        for name, (dtype, dims, _, opcode, rest) in _entry_instructions(
                text_of(one_chip)[slots]).items():
            if opcode in ("copy", "transpose") \
                    and "op_ragged_paged_attention" in _op_name(rest):
                size = int(np.prod(dims)) * _BYTES[dtype]
                assert size <= 2 ** 20, (name, dtype, dims)

    def test_no_copy_of_the_q_and_k_weights(self, mosaic, one_chip, program,
                                            slots):
        text_of, _ = STEP_PROGRAMS[program]
        found = _entry_instructions(text_of(one_chip)[slots])
        for name, (_, dims, _, opcode, rest) in found.items():
            scope = _op_name(rest)
            if opcode == "copy" and ("/q_proj/" in scope
                                     or "/k_proj/" in scope):
                operand = re.search(r"%([^\s,)]+)", rest).group(1)
                assert not _reads_state(operand, found), (name, dims, scope)

    def test_q_proj_writes_rows(self, mosaic, one_chip, program, slots):
        text_of, width = STEP_PROGRAMS[program]
        products = [
            (name, dims, layout)
            for name, (_, dims, layout, opcode, rest) in _entry_instructions(
                text_of(one_chip)[slots]).items()
            if opcode in ("fusion", "convolution")
            and _op_name(rest).endswith("self_attn/q_proj/jit(op_linear)"
                                        "/dot_general")]
        assert products
        for name, dims, layout in products:
            assert dims == (slots, width), (name, dims)
            assert layout.startswith("{1,0"), (name, layout)


def _fused_adamw_text(one_chip):
    # one decoder layer's matrices and a norm in one bucket: 78.6 M
    # elements, a row count that is not a multiple of the 512-row block
    shapes = ((HIDDEN, FFN), (HIDDEN, HIDDEN), (HIDDEN, HIDDEN), (HIDDEN,))
    plan = fok.plan_buckets(
        "adam", {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "decoupled": True},
        tuple((s, "float32", "bfloat16", "bfloat16", 0.01) for s in shapes))
    (bucket,) = plan.buckets
    assert bucket.total >= 64 * 2 ** 20
    p = [_sds(one_chip, s, jnp.float32) for s in shapes]
    g = [_sds(one_chip, s, jnp.bfloat16) for s in shapes]
    state = [{"m": x, "v": x} for x in p]
    scalar = _sds(one_chip, (), jnp.float32)

    def apply(p, g, s, lr, step):
        return fok.fused_apply(plan, p, g, s, lr, step, 1.0, 1.0, 0.0,
                               use_pallas=True, condition=False)

    return _compiled("fused_adamw", lambda: jax.jit(apply).lower(
        p, g, state, scalar, scalar).compile().as_text())


def test_fused_adamw_bucket(mosaic, one_chip):
    assert CUSTOM_CALL in _fused_adamw_text(one_chip)


def _custom_call_names(text):
    """The instruction names of the compiled text's Pallas calls, without
    XLA's counter: what a device trace's ``XLA Ops`` line shows."""
    names = set()
    for line in text.splitlines():
        if CUSTOM_CALL in line and "custom-call(" in line:
            m = re.match(r"\s*(?:ROOT\s+)?%?([^\s=]+)\s*=", line)
            names.add(re.sub(r"\.\d+$", "", m.group(1)))
    return names


@pytest.mark.parametrize("text_of, kernel, others", [
    (lambda c: _ragged_text(c, jnp.bfloat16), "ragged_paged_attention", ()),
    (lambda c: _ragged_text(c, jnp.int8), "ragged_paged_attention", ()),
    (lambda c: _flash_text(c, 8), "flash_attention_fwd",
     ("flash_attention_bwd_dq", "flash_attention_bwd_dkv")),
    (lambda c: _flash_text(c, 8), "flash_attention_bwd_dq",
     ("flash_attention_fwd", "flash_attention_bwd_dkv")),
    (lambda c: _flash_text(c, 8), "flash_attention_bwd_dkv",
     ("flash_attention_fwd", "flash_attention_bwd_dq")),
    (_fused_adamw_text, "fused_optimizer", ()),
], ids=["ragged-bf16", "ragged-int8", "flash-fwd", "flash-bwd-dq",
        "flash-bwd-dkv", "fused-adamw"])
def test_custom_call_carries_its_kernels_name(mosaic, one_chip, text_of,
                                              kernel, others):
    # the name= of the pl.pallas_call, plain: under jax.grad too, where a
    # bare name would read jvp_<name>_ and transpose_jvp_<name>__; readers
    # of a device trace look kernels up by these names (ISSUE 26)
    assert _custom_call_names(text_of(one_chip)) == {kernel, *others}
