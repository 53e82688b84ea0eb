"""The reduction from events to busy time, idle gaps by host span and time
by operation, on a hand-written event list."""

from chipbench.trace import Event, UNATTRIBUTED, breakdown, reduce, short_name

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(name, start, dur, plane=DEV, line="XLA Ops"):
    return Event(plane, line, name, float(start), float(dur))


def events():
    return [
        # two steps of 100 ns, a 20 ns hole between them
        ev("chipbench.step", 0, 100, HOST, "main"),
        ev("chipbench.admit", 100, 20, HOST, "main"),
        ev("chipbench.step", 120, 100, HOST, "main"),
        # step 1: ops over [10, 50) and [40, 80) overlap -> [10, 80)
        ev("%fusion.1 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[8]{0} %p)", 10, 40),
        ev("%fwd_flat.3 = bf16[4,8]{1,0} custom-call(s32[4]{0} %a), "
           "custom_call_target=\"tpu_custom_call\"", 40, 40),
        # step 2: one op [130, 210)
        ev("%fusion.7 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[8]{0} %q)", 130, 80),
        # before the first span: outside the window
        ev("%copy.2 = f32[2]{0} copy(f32[2]{0} %z)", -50, 10),
    ]


def test_busy_window_and_ops():
    r = reduce(events(), "chipbench.step")
    assert r.window_s == 220e-9
    assert abs(r.busy_s - (70 + 80) * 1e-9) < 1e-15
    ops = dict(r.ops)
    assert abs(ops["fusion bf16[8,128]"] - 120e-9) < 1e-15
    assert abs(ops["fwd_flat"] - 40e-9) < 1e-15
    assert r.ops[0][0] == "fusion bf16[8,128]"
    assert not any(name.startswith("copy") for name in ops)


def test_gaps_go_to_the_spans_they_lie_under():
    r = reduce(events(), "chipbench.step")
    gaps = dict(r.idle_gaps)
    # [0,10) and [80,100) under step 1, [100,120) under admit, [120,130)
    # and [210,220) under step 2
    assert abs(gaps["chipbench.step"] - 50e-9) < 1e-15
    assert abs(gaps["chipbench.admit"] - 20e-9) < 1e-15
    assert abs(sum(gaps.values()) + r.busy_s - r.window_s) < 1e-15


def test_gap_without_a_span_is_named_so():
    es = [ev("chipbench.step", 0, 10, HOST, "main"),
          ev("chipbench.step", 90, 10, HOST, "main"),
          ev("%a.1 = f32[1]{0} add(f32[1]{0} %x)", 0, 10),
          ev("%a.2 = f32[1]{0} add(f32[1]{0} %x)", 90, 10)]
    r = reduce(es)
    assert dict(r.idle_gaps) == {UNATTRIBUTED: 80e-9}


def test_two_device_planes_are_averaged():
    es = events() + [ev("%fusion.9 = bf16[8,128]{1,0} fusion()", 0, 220,
                        "/device:TPU:1")]
    r = reduce(es, "chipbench.step")
    assert abs(r.busy_s - (150 + 220) / 2 * 1e-9) < 1e-15


def test_nothing_to_read_gives_an_empty_reduction():
    r = reduce([ev("chipbench.step", 0, 10, HOST, "main")])
    assert r.window_s == 0 and r.busy_s == 0 and r.ops == []


def test_breakdown_has_at_most_ten_entries():
    es = [ev("chipbench.step", 0, 1000, HOST, "main")] + [
        ev(f"%op{i}.1 = f32[{i + 1}]{{0}} add(f32[1]{{0}} %x)", 10 * i, 5)
        for i in range(30)]
    b = breakdown(reduce(es))
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10


def test_short_name():
    hlo = ("%fusion.331 = (bf16[4096,32768]{1,0:T(8,128)(2,1)}, "
           "f32[4096,32768]{1,0:T(8,128)}) fusion(f32[4096,32768]{1,0} %m), "
           "kind=kOutput, calls=%fused_computation.476")
    assert short_name(hlo) == "fusion (bf16[4096,32768], f32[4096,32768])"
    call = ("%fwd_flat.1 = bf16[128,8,32,128]{3,2,1,0} custom-call(s32[128]{0}"
            " %g), custom_call_target=\"tpu_custom_call\"")
    assert short_name(call) == "fwd_flat"
    assert len(short_name("%x.1 = " + "f32[1], " * 100 + "tuple()")) <= 120
