"""The span plane's readers (chipbench/reducers/, reduce.py's scopes and
breakdown, trace.py's anchor): on hand-made traces each of the three reducer
kinds and the breakdown's naming give the numbers worked out by hand; on
fixtures/*.plane.json (trimmed copies of real v5e traces of PR 26) they give
what the chip runs printed; the clock anchor maps a host span onto the
window within a stated error; a reader that finds nothing returns nothing,
and a share cannot pass 100%."""
import json
import types

import pytest

from chipbench import manifest, reduce, run
from chipbench import trace as tr
from chipbench.drivers import serve
from chipbench.reducers import (counter_ratio, grouped_ms, scope_share,
                                span_stat)

MF = manifest.load_manifest()
PEAKS = manifest.load_json(manifest.HERE / "peaks.json")["TPU v5 lite"]
CELLS = {"fedavg": "resnet18gn_fedavg_c100", "fedlora": "olmo1b_fedlora_s8",
         "serve": "olmo1b_decode_chat"}
STEP, ADMIT = "jit__step_all(1)", "jit__admit(2)"


def breakdown(trace):
    return reduce.breakdown(trace, states=serve.Driver.states)


def plane(kind):
    return manifest.load_json(manifest.HERE / "fixtures" / f"{kind}.plane.json")


def read(kind, name, trace=None):
    trace = trace or plane(kind)
    cell = manifest.Cell(MF, CELLS[kind])
    spec = cell.metric_file(name)
    ctx = {"cell": cell, "log": trace["log"], "peaks": PEAKS,
           "window_s": reduce.window_seconds(trace),
           "busy_s": reduce.busy_seconds(trace)}
    return manifest.find("reducers", spec["reducer"])(spec, trace, ctx)


# ----------------------------------------------------------- hand-made trace
def tiny():
    """A 1,000 ns window: one step program (100..700) whose operations are
    attention 100, a kv write 50, two unscoped pool copies 150 + 100 under a
    loop wrapper, and an admit program (750..950) with one scoped op."""
    scopes = {          # by module: a program event's name less its fingerprint
        "jit__step_all": {"while.1": "jit(_step_all)/while",
               "fusion.2": "jit(_step_all)/while/body/decode.attn/dot_general",
               "fusion.3": "jit(_step_all)/while/body/decode.kv_write/scatter",
               "copy.4": "jit(_step_all)/while/body/dynamic_update_slice",
               "copy.5": ""},
        "jit__admit": {"fusion.2":
                       "jit(_admit)/decode.mlp/vmap(decode.sample)/add"}}
    ops = [["while.1", 100, 600], ["fusion.2", 100, 100],
           ["fusion.3", 200, 50], ["copy.4", 250, 150], ["copy.5", 450, 100],
           ["fusion.2", 800, 100]]
    return {"chips": [{"programs": [[STEP, 100, 600], [ADMIT, 750, 200]],
                       "ops": ops, "scopes": scopes}],
            "host": [[reduce.WINDOW_SPAN, 0, 1000],
                     ["chipbench.round", 0, 1000]],
            "program": [], "log": {}}


def test_leaf_is_the_innermost_scope_even_inside_parentheses():
    assert reduce.leaf("jit(f)/fed.collect/while/body/vmap(fed.local_sgd)/"
                   "jvp(lm.head)/mul") == "lm.head"
    assert reduce.leaf("jit(round_body)/fed.finalize/fed.health/vmap()/x") \
        == "fed.health"
    assert reduce.leaf("jit(f)/while/body/dynamic_update_slice") == ""
    assert reduce.kind_of("bitcast_add_fusion.12") == "bitcast_add_fusion"


def test_scope_share_by_leaf_unscoped_and_program():
    t, c = tiny(), {"busy_s": 700e-9}
    step = {"programs": ["^jit__step_all"], "over": "programs"}
    # unscoped 150 + 100, kv_write 50, of the 400 ns the step program's
    # operations take (the loop wrapper left out); the same instruction
    # name in the admit program is another operation
    assert scope_share({**step, "unscoped": True,
                           "leaf": ["decode.kv_write"]}, t, c) \
        == pytest.approx(100 * 300 / 400)
    assert scope_share({**step, "leaf": ["decode.attn"]}, t, c) \
        == pytest.approx(100 * 100 / 400)
    # over the window's busy time, any program: sample is innermost there
    assert scope_share({"leaf": ["decode.sample"]}, t, c) \
        == pytest.approx(100 * 100 / 700)
    assert scope_share({"holds": ["decode.mlp"]}, t, c) \
        == pytest.approx(100 * 100 / 700)
    assert scope_share({"leaf": ["decode.mlp"]}, t, c) == 0.0


def test_scope_share_is_a_union_and_refuses_to_pass_100():
    t = tiny()
    t["chips"][0]["ops"] += [["fusion.2", 120, 60]]     # overlaps itself
    spec = {"programs": ["^jit__step_all"], "over": "programs",
            "leaf": ["decode.attn"]}
    assert scope_share(spec, t, {}) == pytest.approx(100 * 100 / 400)
    with pytest.raises(ValueError, match="passes 100%"):
        scope_share({"leaf": ["decode.attn"]}, t, {"busy_s": 50e-9})


def test_readers_that_find_nothing_return_nothing_and_never_raise():
    t = tiny()
    t["chips"][0]["scopes"] = {}            # the parent: a program unscoped
    c = {"busy_s": 700e-9, "log": {}, "cell": types.SimpleNamespace()}
    assert scope_share({"leaf": ["decode.attn"]}, t, c) is None
    assert scope_share({"unscoped": True}, t, c) is None
    del t["chips"][0]["scopes"], t["program"]   # a trace of before this PR
    assert scope_share({"holds": ["rematted_computation"]}, t, c) is None
    assert span_stat({"spans": ["serving.engine.queue"]}, t, c) is None
    assert counter_ratio({"counter": "a", "over": "b"}, t, c) is None
    bd = breakdown(t)                    # and the old naming stays
    assert dict(bd["device_ops"]) == pytest.approx(
        {"copy": 250e-9, "fusion": 250e-9})
    assert bd["idle_gaps"] == [["round", 300e-9]]


def test_span_stat_groups_by_request_and_by_round():
    rows = [["serving.http.in", 0, 2_000_000, "a", {}],
            ["serving.http.out", 50, 1_000_000, "a", {}],
            ["serving.http.in", 60, 4_000_000, "b", {}],
            ["serving.http.out", 70, 5_000_000, "b", {}],
            ["serving.http.in", 80, 9_000_000, "c", {}],   # no out: not whole
            ["serving.engine.queue", 90, 7_000_000, "a", {}]]
    t = {**tiny(), "program": rows}
    spec = {"spans": ["serving.http.in", "serving.http.out"],
            "group_by": "trace_id", "stat": "p95"}
    assert grouped_ms(spec, t) == {"a": 3.0, "b": 9.0}
    assert span_stat(spec, t, {}) == 9.0     # nearest rank above, of two
    assert span_stat({"spans": ["serving.engine.queue"]}, t, {}) == 7.0
    rounds = [[n, 10 * r, d, f"t{r}", {"round": r}]
              for r, ds in enumerate(((1, 2, 3), (2, 2, 2), (10, 1, 1)))
              for n, d in zip(("fed.round.sample", "fed.round.dispatch",
                               "fed.round.observe"),
                              (x * 1_000_000 for x in ds))]
    rounds.append(["fed.round.fetch", 5, 400_000_000, "t0", {"round": 0}])
    spec = {"spans": ["fed.round.sample", "fed.round.dispatch",
                      "fed.round.observe"], "group_by": "round",
            "stat": "median"}
    assert span_stat(spec, {**tiny(), "program": rounds}, {}) == 6.0
    inside = {**spec, "within": "window"}       # rows past the window's end
    late = [[r[0], r[1] + 2000, *r[2:]] for r in rounds]
    assert span_stat(inside, {**tiny(), "program": late}, {}) is None


def test_counter_ratio_takes_its_constant_from_the_traffic_file():
    cell = manifest.Cell(MF, "olmo1b_decode_chat")
    spec = cell.metric_file("slot_occupancy.serve")
    log = {"counters": {"serving.engine.slot_steps": 600,
                        "serving.engine.steps": 75}}
    slots = cell.traffic["serve"]["decode_slots"]
    assert counter_ratio(spec, {}, {"cell": cell, "log": log}) \
        == pytest.approx(100 * 600 / (75 * slots))
    log["counters"]["serving.engine.steps"] = 0
    assert counter_ratio(spec, {}, {"cell": cell, "log": log}) is None


def test_breakdown_names_operations_by_scope_and_gaps_by_program_span():
    t = tiny()
    # the engine fetched between the programs; a request waited all along
    t["program"] = [["serving.engine.fetch", 690, 70, "x", {"kind": "step"}],
                    ["serving.engine.first_fetch", 0, 1000, "r", {}],
                    ["serving.request", 0, 1000, "r", {}]]
    bd = breakdown(t)
    assert dict(bd["device_ops"]) == pytest.approx({
        "copy": 250e-9, "decode.attn:fusion": 100e-9,
        "decode.sample:fusion": 100e-9, "decode.kv_write:fusion": 50e-9})
    assert bd["device_ops"][0][0] == "copy"     # wrappers left out, sorted
    # gaps: 0..100 and 900..1000 under the harness's round span only;
    # 700..800 is cut where the engine's fetch (690..760) ends: 60 ns
    # under the fetch, the innermost, and the 40 ns after it under round
    assert dict(bd["idle_gaps"]) == pytest.approx(
        {"round": 240e-9, "serving.engine.fetch": 60e-9})
    assert all(len(v) <= 10 for v in bd.values())


def test_coverage_reads_only_the_programs_whose_text_was_read():
    t = tiny()
    del t["chips"][0]["scopes"]["jit__admit"]       # as on the chip: the step
    t["chips"][0]["ops"] += [["fusion.9", 560, 30]]     # not in the text
    got = reduce.coverage(t)
    assert got["by_scope_s"] == pytest.approx({
        "(none)": 280e-9, "decode.attn": 100e-9, "decode.kv_write": 50e-9})
    assert got["unscoped_kinds_s"] == pytest.approx(
        {"copy": 250e-9, "fusion": 30e-9})
    assert got["unmapped_s"] == pytest.approx(30e-9)


def test_trim_keeps_each_scope_and_kind_with_its_path_and_the_programs_rows():
    t = tiny()
    t["program"] = [["serving.engine.fetch", 690, 70, "x", {}],
                    ["serving.request", -5000, 100, "r", {}]]  # before it
    t["chips"][0]["ops"] += [["fusion.2", 50_000, 100]]     # past the window
    # three more steps of the same five operations, 10 us apart
    for k in range(1, 4):
        t["chips"][0]["ops"] += [[n, s + 10_000 * k, d] for n, s, d in
                                 tiny()["chips"][0]["ops"][:5]]
        t["chips"][0]["programs"] += [[STEP, 100 + 10_000 * k, 600]]
    t["host"][0][2] = 40_000
    small = reduce.trim(t, keep_ops=5, per_kind=1)
    chip = small["chips"][0]
    names = [o[0] for o in chip["ops"]]
    # the first five (no wrapper among them: the step's four and the
    # admit's one), then one more of each (scope, kind, program), copy.5
    # being of copy.4's, and one loop wrapper
    assert names.count("fusion.2") == 3 and names.count("copy.5") == 1
    assert names.count("while.1") == 1 and len(names) == 9
    assert chip["ops"] == sorted(chip["ops"], key=lambda o: o[1])
    assert chip["scopes"]["jit__step_all"]["fusion.3"].endswith("scatter")
    assert len(chip["programs"]) == 5 and small["program"] == t["program"]
    assert reduce.window_of(small) == reduce.window_of(t)


def test_anchor_maps_a_perf_counter_span_onto_the_window():
    """The window annotation began at 5,000,000 ns of the device's
    timebase when perf_counter read 100.0 s; a span timed 100.25..100.75 s
    lands 250 ms into the window, and an annotation of the same name that
    the profiler stamped 40 us later measures the anchor's error."""
    spans = [types.SimpleNamespace(name="fed.round.fetch", start=100.25,
                                   end=100.75, trace_id="t",
                                   meta={"round": 4, "ids": [1, 2]}),
             types.SimpleNamespace(name="early", start=90.0, end=99.0,
                                   trace_id="u", meta={})]
    rows = tr.program_rows(spans, 100.0, 5_000_000, since=99.5)
    assert rows == [["fed.round.fetch", 255_000_000, 500_000_000, "t",
                     {"round": 4}]]
    errs = tr.anchor_error_us(
        rows, [["fed.round.fetch", 255_040_000, 499_000_000],
               ["fed.round.fetch", 1, 5], ["other", 255_000_000, 1]],
        5_000_000, 905_000_000)
    assert errs == [pytest.approx(40.0)]


def test_plane_tracer_off_is_a_no_op():
    t = tr.Tracer("", 0.0, on=False)
    t.start(), t.open(), t.stop()
    assert not t.active and t.counters == {} and not t.done


def test_the_replica_s_first_token_adds_up_the_five_of_a_whole_request():
    def req(tid, t0, parts):
        rows, at = [], t0
        for name, ms in zip(serve.FIVE, parts):
            rows.append([name, at, ms * 1_000_000, tid, {}])
            at += ms * 1_000_000
        return rows
    rows = req("a", 0, (1, 10, 100, 50, 2)) + req("b", 7, (1, 200, 300, 60, 3))
    rows += [["serving.engine.queue", 3, 5, "c", {}]]      # not whole
    spec = manifest.Cell(MF, CELLS["serve"]).metric_file("ttft_p95_ms.serve")
    assert set(spec["spans"]) == set(serve.FIVE)
    assert grouped_ms(spec, {"program": rows}) == {"a": 163.0, "b": 564.0}
    assert span_stat(spec, {"program": rows}, {}) == 564.0
    assert span_stat(spec, {"program": []}, {}) is None


# ------------------------------------------ the nine entries, in the manifest
NINE = ["agg_share.fedavg", "round_host_ms.fedavg", "recompute_share.fedlora",
        "pool_copy_share.serve", "slot_occupancy.serve",
        "queue_wait_p95_ms.serve", "prefill_p95_ms.serve",
        "first_fetch_p95_ms.serve", "http_first_p95_ms.serve"]


def test_the_nine_entries_are_in_the_manifest_with_readers_found_by_name():
    held = {m["name"]: m for m in MF["per_layer"]}
    assert len(MF["per_layer"]) == 22 and set(NINE) <= set(held)
    assert not (manifest.HERE / "spanplane.json").exists()
    for name in NINE:
        spec = manifest.load_json(manifest.HERE / "metrics" / f"{name}.json")
        assert spec["reducer"] in ("scope_share", "span_stat",
                                   "counter_ratio") and spec["reads"]
        assert callable(manifest.find("reducers", spec["reducer"]))
    by_cell = {c: [m["name"] for m in manifest.metrics_for(MF, c, True)
                   if m["name"] in NINE] for c in CELLS.values()}
    assert [len(by_cell[CELLS[k]]) for k in ("fedavg", "fedlora", "serve")] \
        == [2, 1, 6]
    assert by_cell[CELLS["fedlora"]] == ["recompute_share.fedlora"]


# ------------------------------------ fixtures: trimmed real v5e traces, PR 26
def by_hand(trace, programs, pick):
    """Summed device ns of the picked operations inside the matching
    programs, and of all their operations (a TPU core runs them one after
    another, so a sum is the union)."""
    num = den = 0
    for (_n, _s, dur), prog, path in reduce.scoped_ops(trace):
        if prog.startswith(programs):
            den += dur
            num += dur * bool(pick(reduce.leaf(path), path))
    return num, den


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_plane_fixture_keeps_the_old_keys_and_adds_the_new(kind):
    t = plane(kind)
    assert {"chips", "host", "log", "note"} <= set(t)       # as *.trace.json
    assert {"program", "anchor"} <= set(t) and "counters" in t["log"]
    chip = t["chips"][0]
    assert set(chip) == {"programs", "ops", "scopes"}
    ops = {o[0] for o in chip["ops"]}
    held = {n for v in chip["scopes"].values() for n in v}
    # (the serving cell maps its step program only, not the admit buckets)
    assert held <= ops and len(held) > 0.5 * len(ops)
    # every metric of the cell reads something off it
    cell = manifest.Cell(MF, CELLS[kind])
    for m in manifest.metrics_for(MF, CELLS[kind], traced=True):
        spec = cell.metric_file(m["name"])
        ctx = {"cell": cell, "log": t["log"], "peaks": PEAKS,
               "window_s": reduce.window_seconds(t),
               "busy_s": reduce.busy_seconds(t)}
        assert manifest.find("reducers", spec["reducer"])(
            spec, t, ctx) is not None
    assert t["anchor"]["bracket_us"] < 50
    if t["anchor"]["matched"]:
        # the anchor's measured error, stated in PERF.md as under 1 ms
        assert t["anchor"]["error_us_median"] < 100
        assert t["anchor"]["error_us_worst"] < 1000


def test_agg_share_on_the_fedavg_fixture():
    t = plane("fedavg")
    agg = {"fed.accumulate", "fed.collect", "fed.finalize", "fed.health"}
    num, den = by_hand(t, "jit_round_body", lambda lf, _p: lf in agg)
    got = read("fedavg", "agg_share.fedavg", t)
    assert got == pytest.approx(100 * num / den, rel=1e-3) and 0 < got < 100
    leaves = {reduce.leaf(p) for v in t["chips"][0]["scopes"].values()
              for p in v.values()}
    assert agg | {"fed.local_sgd"} <= leaves


def test_round_host_ms_on_the_fedavg_fixture():
    t = plane("fedavg")
    rows = [r for r in t["program"] if r[0].startswith("fed.round.")]
    rounds = sorted({r[4]["round"] for r in rows})
    assert len(rounds) >= 3
    per = [sum(r[2] for r in rows if r[4]["round"] == k
               and r[0] != "fed.round.fetch") / 1e6 for k in rounds
           if {r[0] for r in rows if r[4]["round"] == k}
           >= {"fed.round.sample", "fed.round.dispatch", "fed.round.observe"}]
    got = read("fedavg", "round_host_ms.fedavg", t)
    assert got == pytest.approx(sorted(per)[len(per) // 2]) or \
        got == pytest.approx(__import__("statistics").median(per))
    assert got == pytest.approx(5.50383)    # as the chip run printed it
    # the wait on the device is what it leaves out
    fetch = [r[2] / 1e6 for r in rows if r[0] == "fed.round.fetch"]
    assert min(fetch) > 10 * got


def test_breakdown_on_the_fedavg_fixture_names_layers_and_program_spans():
    bd = breakdown(plane("fedavg"))
    names = [n for n, _ in bd["device_ops"]]
    assert names[0] != "fusion" and any(n.startswith("fed.local_sgd:")
                                        for n in names)
    idle = dict(bd["idle_gaps"])
    by_program = sum(v for k, v in idle.items() if k.startswith("fed.round."))
    assert by_program >= 0.9 * sum(idle.values())


def test_recompute_share_on_the_fedlora_fixture():
    t = plane("fedlora")
    num, den = by_hand(t, "jit_round_body",
                       lambda _lf, p: "rematted_computation" in p)
    got = read("fedlora", "recompute_share.fedlora", t)
    assert got == pytest.approx(100 * num / den, rel=1e-3) and 0 < got < 100
    paths = [p for v in t["chips"][0]["scopes"].values() for p in v.values()]
    # the recompute, the backward proper and the forward of one layer part
    mlp = [p for p in paths if reduce.leaf(p) == "lm.mlp"]
    assert any("rematted_computation" in p for p in mlp)
    assert any("transpose(jvp" in p and "rematted_computation" not in p
               for p in mlp)
    assert any("transpose" not in p for p in mlp)
    assert [n for n, _ in breakdown(t)["device_ops"]][0].startswith("lm.")


def test_serving_metrics_on_the_serve_fixture():
    t = plane("serve")
    num, den = by_hand(t, "jit__step_all",
                       lambda lf, _p: lf in ("", "decode.kv_write"))
    got = read("serve", "pool_copy_share.serve", t)
    assert got == pytest.approx(100 * num / den, rel=1e-3) and 0 < got < 100
    c = t["log"]["counters"]
    occ = read("serve", "slot_occupancy.serve", t)
    assert occ == pytest.approx(100 * c["serving.engine.slot_steps"]
                                / (c["serving.engine.steps"] * 16))
    assert 0 < occ <= 100
    # every request of the run kept its five spans under one trace id
    whole = read("serve", "ttft_p95_ms.serve", t)
    five = manifest.Cell(MF, CELLS["serve"]).metric_file("ttft_p95_ms.serve")
    assert len(grouped_ms(five, t)) == 100
    parts = {n: read("serve", f"{n}.serve", t) for n in (
        "queue_wait_p95_ms", "prefill_p95_ms", "first_fetch_p95_ms",
        "http_first_p95_ms")}
    # what the chip run printed (call 6 of PR 26; every request's rows kept)
    assert parts == pytest.approx({
        "queue_wait_p95_ms": 93.479012, "prefill_p95_ms": 397.307093,
        "first_fetch_p95_ms": 137.599359, "http_first_p95_ms": 1.853629})
    assert occ == pytest.approx(62.30769230769231)
    # a tail is no sum of tails, but the parts bound the whole
    assert max(parts.values()) < whole <= sum(parts.values())
    assert parts["prefill_p95_ms"] > parts["queue_wait_p95_ms"] \
        > parts["http_first_p95_ms"]
    bd = breakdown(t)
    assert any(n == "decode.attn:paged_attention" for n, _ in bd["device_ops"])
    assert all(not n.startswith("serving.request") for n, _ in bd["idle_gaps"])


# ------------------------------------------- the whole flow, in the sandbox
@pytest.mark.parametrize("kind", sorted(CELLS))
def test_rehearsal_prints_every_metric_old_and_new(kind, capsys):
    rc = run.main(["--workload", CELLS[kind], "--seed", "3", "--seconds",
                   "0.5", "--trace", "1", "--rehearse-cpu"])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    obj = json.loads(lines[-1])
    want = [m["name"] for m in manifest.metrics_for(MF, CELLS[kind], True)]
    assert rc == 0 and sorted(obj["metrics"]) == sorted(want)
    assert any(l.startswith("[chipbench] plane ") for l in lines)
    assert obj["device"]["platform"] == "cpu"       # stamped: no result
    assert all(len(obj["breakdown"][k]) <= 10 for k in obj["breakdown"])


def test_compiled_scopes_of_the_round_program_and_the_step_program():
    """Where the scope paths come from on the chip: the compiled text of
    the programs a driver drove (here at rehearsal sizes, on the CPU)."""
    for cell, module, some in (
            ("resnet18gn_fedavg_c100", "jit_round_body",
             {"fed.local_sgd", "fed.accumulate", "fed.finalize"}),
            ("olmo1b_decode_chat", "jit__step_all",
             {"decode.attn", "decode.mlp", "decode.head"})):
        c = manifest.Cell(MF, cell)
        driver = manifest.find("drivers", c.driver)(c, 3, True)
        driver.setup()
        try:
            held = tr.compiled_scopes(driver.programs())
        finally:
            driver.free()
        assert module in held
        assert some <= {reduce.leaf(p) for p in held[module].values()}
        assert "" in held[module].values()      # compiler-made: no path
