"""The compiled programs name their own device work (ISSUE 39).

`nn.Layer.__call__` traces `forward` under `jax.named_scope(<the key its
parent holds it under>)`, `TrainStep` scopes its optimizer, the serving step
its id gather and its cache writes; whoever makes an executable hands it to
`tracing.note_program`, and `tracing.device_ops()` reads the programs' text
back as one record an instruction. These cases hold the scopes to the
parameters' structured names, the registry to one note an executable and 16
programs, the new spans and attributes to what they say, and eager results
to the bit.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.autograd.engine import no_grad
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit.api import TrainStep, _swap_state
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.observability import tracing

VOCAB = 128


def _llama(layers=2):
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64,
                      intermediate_size=160, num_hidden_layers=layers,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture
def programs():
    """An empty registry and ring for the case, and again after it."""
    tracing.clear()
    yield tracing._PROGRAMS
    tracing.clear()


def _op_names(compiled):
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


def _jitted_forward(model, ids):
    state = list(model.parameters()) + [b for _, b in model.named_buffers()]

    def forward(arrays, ids):
        with _swap_state(state, list(arrays)), no_grad():
            return model(Tensor(ids))._data

    return jax.jit(forward).lower([t._data for t in state], ids).compile()


# -- scopes -------------------------------------------------------------------

def test_scope_key_is_the_name_the_parent_holds_the_layer_under():
    model = _llama()
    assert model._scope_key == "llamaforcausallm"       # no parent: the class
    for name, sub in model.named_sublayers():
        # a LayerList is never called, so its members carry its key
        last = name.rsplit(".", 1)[-1]
        assert sub._scope_key == ("layers/" + last if last.isdigit() else last)
    called = nn.Sequential(nn.Linear(2, 2), nn.ReLU())
    assert [l._scope_key for l in called.children()] == ["0", "1"]
    again = nn.Linear(2, 2)
    called.add_sublayer("head", again)
    assert again._scope_key == "head"
    # held before the list has a parent, and appended after
    blocks = nn.LayerList([nn.Linear(2, 2)])
    assert blocks[0]._scope_key == "layerlist/0"
    called.add_sublayer("blocks", blocks)
    blocks.append(nn.Linear(2, 2))
    assert [l._scope_key for l in blocks] == ["blocks/0", "blocks/1"]


@pytest.mark.parametrize("path", ["layers/0/self_attn/q_proj",
                                  "layers/0/mlp/down_proj",
                                  "layers/1/self_attn/o_proj",
                                  "llamaforcausallm/llama/embed_tokens"])
def test_a_jitted_forward_names_its_instructions_by_module(path):
    model = _llama().eval()
    names = _op_names(_jitted_forward(model, jnp.zeros((1, 8), jnp.int32)))
    assert any(path + "/" in n for n in names), sorted(set(names))[:20]


def test_a_scope_path_is_the_prefix_of_the_parameters_structured_name():
    model = _llama().eval()
    names = _op_names(_jitted_forward(model, jnp.zeros((1, 8), jnp.int32)))
    # the projections' products (attention's own sit under self_attn)
    dots = [n for n in names if n.endswith("jit(op_linear)/dot_general")]
    assert len(set(dots)) == 2 * 7 + 1
    params = {name.rsplit(".", 1)[0].replace(".", "/")
              for name, _ in model.named_parameters()}
    for n in dots:
        # without the root's class, the per-op jit and the primitive
        scope = re.sub(r"/jit\([^)]*\)", "", n).split(
            "llamaforcausallm/", 1)[1].rsplit("/", 1)[0]
        assert scope in params, n


def test_eager_outputs_are_unchanged_to_the_bit():
    """A scope is metadata: the eager forward under it gives the bits the
    same ops give with the scope's context manager taken away."""
    model = _llama().eval()
    ids = Tensor(jnp.asarray(np.arange(8, dtype=np.int32)[None]))
    with no_grad():
        scoped = np.asarray(model(ids)._data)
        real, jax.named_scope = jax.named_scope, \
            lambda name: contextlib.nullcontext()
        try:
            bare = np.asarray(model(ids)._data)
        finally:
            jax.named_scope = real
    assert scoped.tobytes() == bare.tobytes()


# -- the registry -------------------------------------------------------------

def test_the_registry_keeps_the_newest_sixteen_programs(programs):
    compiled = jax.jit(lambda x: x + 1).lower(jnp.zeros(2)).compile()
    for i in range(20):
        tracing.note_program(f"p{i}", compiled)
    assert len(programs) == tracing._PROGRAMS_MAX == 16
    assert {r.program for r in tracing.device_ops()} == {
        f"p{i}" for i in range(4, 20)}


def test_a_program_is_read_once_and_a_thunk_only_when_read(programs):
    calls = []

    def thunk():
        calls.append(1)
        return jax.jit(lambda a, b: jnp.tanh(a @ b)).lower(
            jnp.zeros((4, 8)), jnp.zeros((8, 2))).compile()

    tracing.note_program("late", thunk)
    assert calls == []
    first = tracing.device_ops()
    assert calls == [1] and tracing.device_ops() == first
    assert calls == [1] and programs[0].source is None
    dots = [r for r in first if r.opcode == "dot"]
    assert dots and all(r.has_matmul and r.result_type.startswith("f32[4,2]")
                        for r in dots)
    assert any(r.opcode == "fusion" and not r.has_matmul for r in first)


def test_a_program_without_text_gives_no_record(programs):
    def broken():
        raise RuntimeError("no text")

    tracing.note_program("gone", broken)
    assert tracing.device_ops() == []


def test_a_fusion_that_holds_a_dot_has_matmul():
    text = """HloModule m

%fused (p0: f32[4,8], p1: f32[8,2]) -> f32[4,2] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %p1 = f32[8,2]{1,0} parameter(1)
  %d.1 = f32[4,2]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(f)/layers/3/mlp/up_proj/dot_general"}
  ROOT %t.2 = f32[4,2]{1,0} tanh(%d.1)
}

ENTRY %main (a: f32[4,8], b: f32[8,2]) -> (f32[4,2], f32[4,8]) {
  %a = f32[4,8]{1,0} parameter(0)
  %b = f32[8,2]{1,0} parameter(1)
  %fusion.7 = f32[4,2]{1,0:T(8,128)} fusion(%a, %b), kind=kOutput, calls=%fused, metadata={op_name="jit(f)/layers/3/mlp/up_proj/dot_general"}
  %scan.3 = (f32[4,8]{1,0}, f32[2]{0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/layers/3/mamba/pallas_call"}
  %copy.9 = f32[4,8]{0,1} copy(%a)
  ROOT %out = (f32[4,2]{1,0}, f32[4,8]{0,1}) tuple(%fusion.7, %copy.9)
}
"""
    by_name = {r.instruction: r for r in tracing._parse_hlo("m", text)}
    assert by_name["fusion.7"].has_matmul and by_name["d.1"].has_matmul
    assert by_name["fusion.7"].result_type == "f32[4,2]{1,0:T(8,128)}"
    assert by_name["fusion.7"].op_name.endswith("up_proj/dot_general")
    assert by_name["scan.3"].kernel == "scan" and \
        by_name["scan.3"].result_type == "(f32[4,8]{1,0}, f32[2]{0})"
    assert by_name["copy.9"].opcode == "copy" and \
        not by_name["copy.9"].has_matmul and by_name["copy.9"].op_name == ""
    # what XLA fused into an instruction: the op_names inside what it calls
    assert by_name["fusion.7"].fused_op_names == (
        "jit(f)/layers/3/mlp/up_proj/dot_general",)
    assert by_name["d.1"].fused_op_names == ()


# -- TrainStep ----------------------------------------------------------------

def _train_step(clip=False):
    model = _llama(layers=1)
    crit = lambda logits, labels: paddle.nn.functional.cross_entropy(
        logits.reshape([-1, VOCAB]), labels.reshape([-1]))
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters(),
        grad_clip=nn.ClipGradByGlobalNorm(1.0) if clip else None)
    return TrainStep(model, crit, opt)


def _batch(seed=0):
    ids = np.random.RandomState(seed).randint(0, VOCAB, (2, 8))
    return Tensor(jnp.asarray(ids, jnp.int32))


def test_a_train_step_is_noted_once_and_names_its_phases(programs):
    train = _train_step(clip=True)
    for seed in range(3):
        train((_batch(seed),), (_batch(seed),))
    assert [p.name for p in programs] == ["train_step"]
    ops = tracing.device_ops()
    assert {r.program for r in ops} == {"train_step"}
    names = [r.op_name for r in ops]
    assert any("transpose(jvp(" in n for n in names)
    assert any("jvp(" in n and "transpose(" not in n for n in names)
    assert any("/optimizer.update/" in n for n in names)
    assert any("/optimizer.grad_clip/" in n for n in names)
    # every matmul sits under a module's scope, forward and backward
    for r in ops:
        if r.has_matmul:
            assert "layers" in r.op_name or "lm_head" in r.op_name, r
    back = [r for r in ops if r.has_matmul and "transpose(jvp(" in r.op_name]
    assert any("mlp" in r.op_name for r in back)


def test_a_train_step_span_has_both_children_and_says_which_call_compiled(
        programs):
    train = _train_step()
    for seed in range(3):
        train((_batch(seed),), (_batch(seed),))
    steps = tracing.finished_spans("train.step")
    whole = [s for s in steps if s.name == "train.step"]
    args = [s for s in steps if s.name == "train.step.args"]
    launch = [s for s in steps if s.name == "train.step.launch"]
    assert len(whole) == len(args) == len(launch) == 3
    assert [s.attrs["compiled"] for s in whole] == [1, 0, 0]
    assert all(s.attrs["tokens"] == 16 for s in whole)
    for w, a, l in zip(whole, args, launch):
        assert w.t0_ns == a.t0_ns <= a.t1_ns <= l.t0_ns <= l.t1_ns == w.t1_ns


def test_no_train_step_span_and_no_program_with_tracing_off(programs):
    paddle.set_flags({"FLAGS_tracing": 0})
    try:
        train = _train_step()
        train((_batch(),), (_batch(),))
    finally:
        paddle.set_flags({"FLAGS_tracing": 1})
    assert tracing.finished_spans("train.step") == []
    assert len(programs) == 0 and tracing.device_ops() == []


# -- the serving engine -------------------------------------------------------

def _engine(model, **kw):
    return ContinuousBatchingEngine(model, num_blocks=64, block_size=16,
                                    temperature=0.0, max_batch=4,
                                    token_budget=32, prefill_chunk=16, **kw)


def _overlapped():
    return [s.attrs["overlapped"]
            for s in tracing.finished_spans("serving.step")
            if s.name == "serving.step"]


def test_an_engine_notes_one_program_a_geometry_with_its_scopes(programs):
    model = _llama().eval()
    eng = _engine(model)
    eng.add_request(list(range(1, 9)), max_new_tokens=4)
    eng.run()
    assert len(eng.geometries) == 2
    assert [p.name for p in programs] == ["serving_step"] * 2
    # a second engine over the model shares its executables: nothing new
    again = _engine(model)
    again.add_request(list(range(1, 9)), max_new_tokens=2)
    again.run()
    assert len(programs) == 2
    names = {r.op_name for r in tracing.device_ops()}
    for scope in ("/serving.gather_ids/",
                  "layers/1/self_attn/serving.cache_write/",
                  "layers/0/self_attn/q_proj/", "layers/1/mlp/down_proj/"):
        assert any(scope in n for n in names), scope


def test_serving_step_spans_say_whether_a_step_was_in_flight(programs):
    eng = _engine(_llama().eval())
    eng.add_request(list(range(1, 9)), max_new_tokens=6)
    eng.step()                          # nothing in flight before the first
    eng.step()
    eng.step()
    assert _overlapped() == [0, 1, 1]
    eng._drain()
    eng.step()                          # launched behind nothing again
    assert _overlapped() == [0, 1, 1, 0]
    eng.run()
    assert eng._inflight is None
