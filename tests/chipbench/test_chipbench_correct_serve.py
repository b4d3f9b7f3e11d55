"""`correct` in the serve kind: true on the sound path, false for the control
(the reference in fp8) and for a token altered where it is produced, with the
run otherwise whole. Tiny sizes, CPU, kernels interpreted."""
from chipbench_rehearsal import rehearse

from chipbench import compare, control, manifest

CELL = "olmo1b_decode_chat"
_T = manifest.Cell(manifest.load_manifest(), CELL).traffic
LIMITS = {**_T["limits"], **_T["rehearse"].get("limits", {})}


def test_a_sound_run_is_correct_and_its_line_well_formed(capsys):
    rc, obj = rehearse(capsys, CELL, seconds=2.0)
    assert rc == 0 and obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 3 and list(obj)[-1] == "compared"
    assert {"ttft_p50_ms", "ttft_p90_ms", "gap_p95_ms", "setup_s"} == set(obj["metrics"])


def test_a_traced_rehearsal_reads_the_fixture_through_every_reducer(capsys):
    rc, obj = rehearse(capsys, CELL, seconds=2.0, trace=1)
    assert rc == 0 and 0 < obj["device"]["busy_s"] <= obj["device"]["window_s"]


def test_the_control_and_the_altered_token_are_not_correct():
    rows = control.read(CELL, seed=5, rehearse=True)
    ok, _ = compare.judge(rows["program"], LIMITS)
    assert ok, rows["program"]
    for case in ("control_fp8", "fault_token_altered"):
        ok, _ = compare.judge(rows[case], LIMITS)
        assert not ok, (case, rows[case])


def test_a_token_altered_in_the_engine_comes_out_not_correct(
        capsys, monkeypatch):
    from fedml_tpu.serving.predictor import GreedyLMPredictor

    real = GreedyLMPredictor.predict_stream

    def altered(self, input_json):
        for ev in real(self, input_json):
            if "token" in ev and ev["index"] == 1:
                ev = {**ev, "token": (ev["token"] + 60) % 128}
                wrong = ev["token"]
            elif ev.get("done") and len(ev["generated_tokens"]) > 1:
                toks = list(ev["generated_tokens"])
                toks[1] = wrong
                ev = {**ev, "generated_tokens": toks}
            yield ev

    monkeypatch.setattr(GreedyLMPredictor, "predict_stream", altered)
    rc, obj = rehearse(capsys, CELL, seconds=2.0)
    assert rc == 0 and obj["correct"] is False
