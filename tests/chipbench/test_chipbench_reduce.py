"""The reduction from a trace to per-layer metrics: on the recorded fixture
traces (fixtures/*.plane.json, trimmed copies of real v5e traces) the four
reducer kinds that read the device's two lines give the numbers read off them
by hand, and a union of overlapping intervals never passes the window. (The
three kinds that read what the program says of itself:
test_chipbench_spanplane.)"""
import types

import pytest

from chipbench import manifest, reduce, reducers

PEAKS = manifest.load_json(manifest.HERE / "peaks.json")["TPU v5 lite"]
MF = manifest.load_manifest()


def fixture(kind):
    return manifest.load_json(manifest.HERE / "fixtures" / f"{kind}.plane.json")


def ctx(cell, trace):
    return {"cell": manifest.Cell(MF, cell), "log": trace["log"],
            "peaks": PEAKS, "window_s": reduce.window_seconds(trace),
            "busy_s": reduce.busy_seconds(trace)}


def read(cell, name, trace):
    spec = manifest.Cell(MF, cell).metric_file(name)
    return manifest.find("reducers", spec["reducer"])(
        spec, trace, ctx(cell, trace))


# ---- the fedavg fixture: three rounds of jit_round_body on one v5e
def test_fedavg_fixture_window_is_the_harness_span():
    t = fixture("fedavg")
    assert reduce.window_of(t) == (479927005, 479927005 + 1304799901)
    assert reduce.window_seconds(t) == pytest.approx(1.304799901)


def test_program_device_ms_on_the_fedavg_fixture():
    # the three executions last 426.83 .. 426.92 ms; their mean, by hand:
    t = fixture("fedavg")
    durs = [p[2] for p in t["chips"][0]["programs"]
            if p[0].startswith("jit_round_body")]
    assert durs == [426848747, 426916671, 426828703]
    assert read("resnet18gn_fedavg_c100", "round_device_ms.fedavg",
                t) == pytest.approx(426.864707)


def test_program_gap_ms_on_the_fedavg_fixture():
    # round 1 ends at 482948569 + 426848747 = 909797316 ns, round 2 starts
    # 7715429 ns later; four small programs (594 + 674 + 595 + 4852 ns)
    # run in between and are not idle time: 7.708714 ms. The next gap is
    # 8425118 - 6703 ns = 8.418415 ms; the median of two is their mean.
    t = fixture("fedavg")
    assert read("resnet18gn_fedavg_c100", "round_gap_ms.fedavg",
                t) == pytest.approx((7.708714 + 8.418415) / 2, abs=1e-6)


def test_mfu_on_the_fedavg_fixture():
    # 28,800 samples x (3 x 1.1108 - 0.0035) GFLOP over 1.3048 s x 197 T
    t = fixture("fedavg")
    assert read("resnet18gn_fedavg_c100", "mfu.fedavg",
                t) == pytest.approx(37.29888070568051)


# ---- the fedlora fixture: three rounds, Mosaic flash kernels among the ops
def test_fedlora_fixture_program_and_mfu():
    t = fixture("fedlora")
    # 1696764874, 1696762521 and 1696766131 ns; 98,304 tokens in 5.1019 s
    assert read("olmo1b_fedlora_s8", "round_device_ms.fedlora",
                t) == pytest.approx(1696.7645086666666)
    assert read("olmo1b_fedlora_s8", "mfu.fedlora",
                t) == pytest.approx(50.09982367198908)


def test_kernel_roofline_on_the_fedlora_fixture():
    t = fixture("fedlora")
    ev = [o for o in t["chips"][0]["ops"] if o[0].startswith("flash_fwd")]
    assert ev, "the fixture lost its kernel events"
    # one causal forward at b 2, t 2,048, 16 heads of 128: 2 b h t^2 d FLOPs
    least = 2.0 * 2 * 16 * 2048 * 2048 * 128 / 197e12
    want = 100 * least * len(ev) / (sum(o[2] for o in ev) / 1e9)
    got = read("olmo1b_fedlora_s8", "flash_fwd_roofline", t)
    assert got == pytest.approx(want) and 20 < got < 60


# ---- hand-made traces, one small case for each reducer kind
def tiny(programs, ops, host=None):
    return {"chips": [{"programs": programs, "ops": ops}],
            "host": host or [[reduce.WINDOW_SPAN, 0, 1000]], "log": {}}


def test_union_of_overlapping_intervals_never_passes_the_window():
    ops = [["while.1", 100, 800], ["fusion.2", 150, 100],
           ["fusion.3", 200, 300], ["copy.4", 950, 200]]   # runs past the end
    t = tiny([], ops)
    assert reduce.union_ns(reduce.clipped(ops, 0, 1000)) == 800 + 50
    assert reduce.busy_seconds(t) == pytest.approx(850e-9)
    assert sum(o[2] for o in ops) > 1000 > 850     # a sum would pass it
    assert reduce.busy_seconds(tiny([], [])) == 0.0


def test_program_device_ms_per_count_from_the_log():
    t = tiny([["jit__admit(1)", 10, 2_000_000], ["jit__admit(1)", 3_000_000,
              4_000_000], ["jit_other(2)", 8_000_000, 1]], [],
             [[reduce.WINDOW_SPAN, 0, 10_000_000]])
    spec = {"programs": ["^jit__admit"], "per": "admitted"}
    assert reducers.program_device_ms(spec, t, {"log": {"admitted": 3}}) == 2.0
    assert reducers.program_device_ms(spec, t, {"log": {"admitted": 0}}) is None
    med = {"programs": ["^jit__admit"], "per": "execution", "stat": "median"}
    assert reducers.program_device_ms(med, t, {}) == 3.0


def test_program_gap_ms_leaves_out_other_programs_and_long_waits():
    progs = [["jit_step(1)", 0, 100], ["jit_admit(2)", 150, 40],
             ["jit_step(1)", 300, 100], ["jit_step(1)", 5_000_400, 100],
             ["jit_step(1)", 5_000_700, 100]]
    t = tiny(progs, [], [[reduce.WINDOW_SPAN, 0, 6_000_000]])
    # gaps: 200 - 40 (the admit) = 160 ns, 5,000,000 ns, 200 ns
    spec = {"programs": ["^jit_step"]}
    assert reducers.program_gap_ms(spec, t, {}) == pytest.approx(200e-6)
    spec["ignore_gaps_over_ms"] = 1.0
    assert reducers.program_gap_ms(spec, t, {}) == pytest.approx(180e-6)


def test_a_reader_that_finds_nothing_returns_nothing():
    t = tiny([["jit_round_body(1)", 0, 10]], [["fusion.1", 0, 10]])
    c = {"cell": types.SimpleNamespace(), "log": {}, "peaks": PEAKS,
         "window_s": 1e-6, "busy_s": 1e-8}
    assert reducers.program_device_ms(
        {"programs": ["^jit_absent"]}, t, c) is None
    assert reducers.program_gap_ms({"programs": ["^jit_round"]}, t, c) is None
    assert reducers.kernel_roofline(
        {"kernels": ["flash_fwd"], "work": "flash_fwd_call"}, t, c) is None


def test_mfu_over_busy_seconds_and_breakdown_form():
    cell = manifest.Cell(MF, "olmo1b_decode_chat")
    t = fixture("fedlora")
    c = {"cell": cell, "log": {"processed_tokens": 1000}, "peaks": PEAKS,
         "window_s": 4.0, "busy_s": 2.0}
    got = reducers.mfu({"work": "decode_flops", "over": "busy_s"}, t, c)
    params = 16 * (4 * 2048 ** 2 + 3 * 2048 * 8192) + 2048 * 50304
    assert got == pytest.approx(100 * 2 * params * 1000 / (2.0 * 197e12))
    bd = reduce.breakdown(t)
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in bd.values())
    assert not any(n.startswith("while") for n, _ in bd["device_ops"])
    assert bd["idle_gaps"] and all(s >= 0 for _, s in bd["idle_gaps"])


def test_op_name_keeps_the_instruction():
    text = ('%flash_fwd.16 = bf16[32,2048,128]{2,1,0:T(8,128)(2,1)} '
            'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
    assert reduce.op_name(text) == "flash_fwd.16"
    assert reduce.op_name("%fusion.3 = f32[] fusion(%x)") == "fusion.3"
