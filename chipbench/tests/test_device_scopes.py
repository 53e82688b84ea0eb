"""Device time by the program's own names: the join of trace events to the
program's records, what is ambiguous, the three readings of an ``op_name``
and a record, and that the groups partition the busy time, on hand-written
lists."""

from typing import NamedTuple

import pytest

from chipbench import device_scopes as ds
from chipbench import trace
from chipbench.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


class Rec(NamedTuple):
    """A record as ``tracing.device_ops()`` gives it."""
    program: str
    instruction: str
    result_type: str
    opcode: str
    op_name: str
    kernel: str = ""
    has_matmul: bool = False
    fused_op_names: tuple = ()


def op(name, start, dur, plane=DEV):
    return Event(plane, "XLA Ops", name, float(start), float(dur))


def host(name, start, dur):
    return Event(HOST, "main", name, float(start), float(dur))


STEP = "jit(serving_step)/llamaforcausallm/llama/"
UP = Rec("serving_step", "fusion.7", "bf16[256,14336]{1,0:T(8,128)(2,1)}",
         "fusion", STEP + "layers/3/mlp/up_proj/jit(op_linear)/dot_general",
         has_matmul=True)
WRITE = Rec("serving_step", "fusion.9", "bf16[262144,8,128]{2,1,0}", "fusion",
            STEP + "layers/3/self_attn/serving.cache_write/"
            "jit(op_paged_cache_write)/scatter")
KERNEL = Rec("serving_step", "ragged_paged_attention.16",
             "bf16[96,8,32,128]{3,2,1,0}", "custom-call",
             STEP + "layers/3/self_attn/jit(op_ragged_paged_attention)/"
             "ragged_paged_attention/pallas_call",
             kernel="ragged_paged_attention")
COPY = Rec("serving_step", "copy.4", "bf16[4096,4096]{0,1}", "copy",
           STEP + "layers/3/self_attn/q_proj/jit(op_linear)/dot_general")
BARE = Rec("serving_step", "copy.8", "bf16[256,32,128]{2,1,0}", "copy", "")


# -- op_name and record -> scope, phase, kind ----------------------------------

@pytest.mark.parametrize("op_name, scope", [
    (UP.op_name, "llamaforcausallm/llama/layers/*/mlp/up_proj"),
    ("jit(step)/jvp(layer0)/mlp.up/dot_general", "layer0/mlp.up"),
    ("jit(step)/transpose(jvp(layers))/12/mlp/down_proj/jit(op_linear)/"
     "transpose", "layers/*/mlp/down_proj"),
    ("jit(step)/optimizer.update/sub", "optimizer.update"),
    ("jit(step)/jvp()/tanh", ""),
    ("reduce_sum", ""),
    ("", ""),
])
def test_scope_of(op_name, scope):
    assert ds.scope_of(op_name) == scope


@pytest.mark.parametrize("op_name, phase", [
    ("jit(step)/jvp(layers)/0/mlp/dot_general", "forward"),
    ("jit(step)/transpose(jvp(layers))/0/mlp/dot_general", "backward"),
    ("jit(step)/optimizer.update/mul", "optimizer"),
    ("jit(step)/optimizer.grad_clip/sqrt", "optimizer"),
    ("jit(step)/jit(_threefry_split)/shift_left", "other"),
    (UP.op_name, "other"),
    ("", "other"),
])
def test_phase_of(op_name, phase):
    assert ds.phase_of(op_name) == phase


@pytest.mark.parametrize("record, kind", [
    (UP, "matmul"), (WRITE, "other"), (COPY, "copy"), (BARE, "copy"),
    (KERNEL, "kernel:ragged_paged_attention"),
    (Rec("p", "transpose.2", "f32[8,4]{1,0}", "transpose", "x"), "copy"),
    # the compiler's prefetch: the core waits for its end
    (Rec("p", "slice-done.3", "bf16[640,10240]{1,0}", "slice-done", ""),
     "copy"),
    # as a loaded executable's text prints it
    (Rec("p", "slice-done.19", "bf16[640,10240]{1,0}", "async-done", ""),
     "copy"),
    (Rec("p", "all-gather-done.2", "bf16[8,4]{1,0}", "async-done", ""),
     "other"),
    # a kernel is a kernel even where its body holds a product
    (KERNEL._replace(has_matmul=True), "kernel:ragged_paged_attention"),
])
def test_kind_of(record, kind):
    assert ds.kind_of(record) == kind


def test_a_record_without_op_name_keeps_its_kind():
    assert ds.fact_of(BARE) == ds.Fact(ds.NO_OP_NAME, "other", "copy", False)


def test_a_fusion_says_what_of_another_phase_is_inside_it():
    step = "jit(step)/"
    grad = Rec("train_step", "fusion.5", "(bf16[8,4]{1,0}, f32[8,4]{1,0})",
               "fusion", step + "transpose(jvp(layers))/0/mlp/up_proj/"
               "jit(op_linear)/dot_general", has_matmul=True,
               fused_op_names=(
                   step + "optimizer.update/mul", step + "optimizer.update/sub",
                   step + "transpose(jvp(layers))/0/mlp/up_proj/"
                   "jit(op_linear)/dot_general",
                   step + "jit(_where)/select_n"))
    assert ds.fact_of(grad) == ds.Fact(
        "layers/*/mlp/up_proj", "backward", "matmul", True, ("optimizer",))
    assert ds.fact_of(UP).inside == ()


# -- the join -----------------------------------------------------------------

def test_an_event_is_parsed_to_its_instruction_and_result_type():
    line = ("%fusion.7 = bf16[256,14336]{1,0:T(8,128)(2,1)S(1)} "
            "fusion(%p.1, %p.2), kind=kOutput, calls=%fused_computation.3")
    assert ds.parse_event(line) == ("fusion.7", "bf16[256,14336]")
    pair = ("%fusion.2 = (f32[256]{0:T(256)}, bf16[1,256,4096]{2,1,0}) "
            "fusion(%a), kind=kOutput")
    assert ds.parse_event(pair) == ("fusion.2", "(f32[256],bf16[1,256,4096])")
    assert ds.parse_event("fusion.7") == ("fusion.7", None)
    assert ds.normal_type(UP.result_type) == "bf16[256,14336]"


def test_join_is_on_the_pair_then_on_the_instruction_alone():
    idx = ds.index([UP, WRITE, KERNEL, COPY])
    up = ds.join("%fusion.7 = bf16[256,14336]{1,0} fusion(%a, %b)", idx)
    assert up == ds.fact_of(UP) and up.kind == "matmul"
    # another layout in the trace's line than in the program's text
    assert ds.join("%fusion.7 = bf16[256,14336]{0,1:T(4,128)} fusion(%a)",
                   idx) == up
    # a type that is not on record: the instruction alone
    assert ds.join("%fusion.7 = bf16[8,8]{1,0} fusion(%a)", idx) == up
    assert ds.join("fusion.7", idx) == up
    assert ds.join("%fusion.8 = bf16[256,14336]{1,0} fusion(%a)", idx) is None


def test_two_programs_that_disagree_leave_an_event_unnamed():
    # the 512-slot program numbers its instructions anew: fusion.7 is
    # another module's product there, fusion.9 the same pool write
    other_up = UP._replace(
        result_type="bf16[512,14336]{1,0}",
        op_name=UP.op_name.replace("up_proj", "gate_proj"))
    idx = ds.index([UP, WRITE, other_up, WRITE])
    at_256 = ds.join("%fusion.7 = bf16[256,14336]{1,0} fusion(%a)", idx)
    at_512 = ds.join("%fusion.7 = bf16[512,14336]{1,0} fusion(%a)", idx)
    assert at_256.scope.endswith("up_proj")
    assert at_512.scope.endswith("gate_proj")
    # by the instruction alone the two disagree
    assert ds.join("fusion.7", idx) is None
    assert ds.join("%fusion.7 = bf16[8]{0} fusion(%a)", idx) is None
    # the same pair in both programs, and they agree
    assert ds.join("%fusion.9 = bf16[262144,8,128]{2,1,0} fusion(%a)",
                   idx) == ds.fact_of(WRITE)
    # the same pair in both programs, and they disagree
    clash = WRITE._replace(op_name=STEP + "layers/3/mlp/jit(op_add)/add")
    assert ds.join("%fusion.9 = bf16[262144,8,128]{2,1,0} fusion(%a)",
                   ds.index([WRITE, clash])) is None
    # layers collapse: the same module in another layer agrees
    layer5 = WRITE._replace(op_name=WRITE.op_name.replace("/3/", "/5/"))
    assert ds.join("fusion.9", ds.index([WRITE, layer5])) == ds.fact_of(WRITE)


# -- the partition of the busy time ---------------------------------------------

def _events():
    line = lambda r: f"%{r.instruction} = {r.result_type} {r.opcode}(%a)"
    return [
        host("chipbench.step", 1000, 1000), host("chipbench.step", 2000, 900),
        host("chipbench.admit", 900, 50),
        op(line(UP), 900, 200),             # begins before the window: 100
        op(line(KERNEL), 1100, 300),
        op(line(WRITE), 1350, 100),         # 50 of it under the kernel: 50
        op(line(COPY), 1500, 100),
        op(line(BARE), 1700, 100),
        op("%fusion.99 = f32[4]{0} fusion(%a)", 2000, 200),   # no record
        op(line(UP), 2800, 200),            # ends after the window: 100
    ]


def test_the_groups_partition_the_busy_time():
    r = trace.reduce(_events(), "chipbench.step")
    assert ds.window_of(r) == (1000.0, 2900.0)
    parts = ds.partition(r.events, ds.window_of(r))
    assert [s * 1e9 for _, s in parts] == pytest.approx(
        [100, 300, 50, 100, 100, 200, 100])
    assert sum(s for _, s in parts) == pytest.approx(r.busy_s)
    a = ds.reduce(r, [UP, WRITE, KERNEL, COPY, BARE], steps=2)
    line = ds.summary(a)
    assert line["groups_over_busy"] == pytest.approx(1.0)
    assert sum(line["phase_ms_a_step"].values()) == pytest.approx(
        line["busy_ms_a_step"])
    by_kind = {}
    for fact, s in a.named:
        by_kind[fact.kind] = by_kind.get(fact.kind, 0.0) + s
    assert sum(by_kind.values()) + a.unnamed_s == pytest.approx(r.busy_s)
    assert by_kind["matmul"] * 1e9 == pytest.approx(200)
    assert by_kind["copy"] * 1e9 == pytest.approx(200)
    # unnamed: the event without a record; the one whose record has no
    # op_name is named by its kind under a scope of its own
    assert a.unnamed == [("fusion f32[4]", pytest.approx(200e-9))]
    assert line["no_op_name_s"] * 1e9 == pytest.approx(100)
    assert [ds.NO_OP_NAME, "copy"] in [p[:2] for p in line["pairs"]]
    assert line["fused_ms_a_step"] == {}
    assert line["pairs"][0][:2] == [
        "llamaforcausallm/llama/layers/*/self_attn/ragged_paged_attention",
        "kernel:ragged_paged_attention"]
    assert line["matmul_ms_by_scope"] == [
        ["llamaforcausallm/llama/layers/*/mlp/up_proj", pytest.approx(1e-4)]]
    named = {name: scopes for name, _, scopes in line["ops"]}
    assert named["fusion bf16[262144,8,128]"][0][0].endswith(
        "self_attn/serving.cache_write")
    assert named["copy bf16[4096,4096]"][0][:2] == [
        "llamaforcausallm/llama/layers/*/self_attn/q_proj", "copy"]


def test_two_planes_are_averaged_as_busy_time_is():
    evs = _events() + [op("%fusion.7 = bf16[256,14336]{1,0} fusion(%a)",
                          1000, 500, plane="/device:TPU:1")]
    r = trace.reduce(evs, "chipbench.step")
    parts = ds.partition(r.events, ds.window_of(r))
    assert sum(s for _, s in parts) == pytest.approx(r.busy_s)


class _Run:
    def __init__(self, reduced, steps):
        self.reduced, self.traced_steps = reduced, steps


def test_the_readers_read_what_the_metrics_name(monkeypatch, capsys):
    from paddle_tpu.observability import tracing
    records = [UP, WRITE, KERNEL, COPY, BARE]
    monkeypatch.setattr(tracing, "device_ops", lambda: records,
                        raising=False)
    run = _Run(trace.reduce(_events(), "chipbench.step"), [{}, {}])
    matmul = ds.ms_where(run, lambda f: f.has_matmul)
    layout = ds.ms_where(run, lambda f: f.kind == "copy"
                         or ds.CACHE_WRITE in f.scope.split("/"))
    assert matmul == pytest.approx(200e-9 * 1e3 / 2)
    assert layout == pytest.approx(250e-9 * 1e3 / 2)
    assert ds.unnamed_share_pct(run) == pytest.approx(100 * 200 / 950)
    out = capsys.readouterr().out
    assert out.count('"event": "device_scopes"') == 1      # once a run


def test_a_program_without_records_gives_nothing(monkeypatch, capsys):
    from paddle_tpu.observability import tracing
    run = _Run(trace.reduce(_events(), "chipbench.step"), [{}, {}])
    monkeypatch.delattr(tracing, "device_ops", raising=False)
    assert ds.ms_where(run, lambda f: True) is None
    assert ds.unnamed_share_pct(run) is None
    empty = _Run(trace.reduce(_events(), "chipbench.step"), [{}, {}])
    monkeypatch.setattr(tracing, "device_ops", lambda: [], raising=False)
    assert ds.analyse(empty) is None
    assert ds.analyse(_Run(None, [])) is None
    assert capsys.readouterr().out == ""
