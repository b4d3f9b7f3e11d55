"""graftlint (ISSUE 13): the static-analysis plane.

Two contracts per rule, driven by the staged fixtures under
tests/lint_fixtures/:
  - POSITIVE: every fixture line marked `# FINDING` produces exactly one
    finding of the rule (the fixture fails without the rule), and nothing
    else in the fixture does;
  - SUPPRESSED-NEGATIVE: the fixture's `# graftlint: disable=<rule>`
    lines stage the same defect and are counted suppressed, not reported.

Plus the gate that makes the plane self-enforcing: graftlint over the
WHOLE package tree (README doc surfaces included) reports zero findings
— tier-1's version of the Docker build hook and the `lint_clean`
diagnosis probe.

Everything here is pure stdlib-ast — no jax, so the file costs ~2s of
the tier-1 budget.
"""
import json
import os
import re

import pytest

from fedml_tpu.analysis import render_json, render_text, run_lint
from fedml_tpu.analysis.core import all_rules, edit_distance

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")


def _marked_lines(*relpath) -> set:
    """1-indexed lines carrying a `# FINDING` marker in a fixture file."""
    with open(os.path.join(FIXTURES, *relpath)) as f:
        return {i for i, line in enumerate(f, 1) if "# FINDING" in line}


def _lint_fixture(tree, rule, extra_docs=None):
    return run_lint([os.path.join(FIXTURES, tree)], rules=[rule],
                    extra_docs=extra_docs or {})


# ------------------------------------------------------------ per-rule
def test_donation_after_use_fixture():
    findings, stats = _lint_fixture("trace/donation.py",
                                    "donation-after-use")
    assert {f.line for f in findings} == _marked_lines("trace",
                                                       "donation.py")
    assert all(f.rule == "donation-after-use" for f in findings)
    # the suppressed twin of `bad` stages the same defect
    assert stats["suppressed"] == 1
    # the self-attribute variant names the donated attribute
    assert any("`self._carry`" in f.message for f in findings)


def test_retrace_hazard_fixture():
    findings, stats = _lint_fixture("trace/retrace.py", "retrace-hazard")
    assert {f.line for f in findings} == _marked_lines("trace",
                                                       "retrace.py")
    assert stats["suppressed"] == 1
    assert any("shard_map" in f.message for f in findings)


def test_in_trace_purity_fixture():
    findings, stats = _lint_fixture("trace/purity.py", "in-trace-purity")
    assert {f.line for f in findings} == _marked_lines("trace",
                                                       "purity.py")
    assert stats["suppressed"] == 1
    msgs = " ".join(f.message for f in findings)
    # transitive reach (called helper), direct clock, scanned body
    assert "_noise" in msgs and "traced_step" in msgs \
        and "scan_body" in msgs


def test_lock_discipline_fixture():
    findings, stats = _lint_fixture("locks", "lock-discipline")
    assert {f.line for f in findings} == _marked_lines("locks", "serving",
                                                       "pool.py")
    assert stats["suppressed"] == 1
    kinds = {f.message.split()[1] for f in findings}   # read / written
    assert kinds == {"read", "written"}


def test_lock_discipline_scoped_to_threaded_tiers():
    # the same class OUTSIDE serving/ or comm/ is out of scope — copy the
    # fixture to a neutral dir name and expect silence
    import shutil
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        os.makedirs(os.path.join(d, "utils"))
        shutil.copy(os.path.join(FIXTURES, "locks", "serving", "pool.py"),
                    os.path.join(d, "utils", "pool.py"))
        findings, _ = run_lint([d], rules=["lock-discipline"],
                               extra_docs={})
    assert findings == []


def test_lock_discipline_survives_subset_scans():
    # scanning the serving dir itself (or one file in it) must NOT
    # silently disable the rule: scoping rides the absolute path, so the
    # engine's 8 justified suppressions are still counted — the exact
    # workflow of a developer lint-checking only the file they edited
    pkg = os.path.join(os.path.dirname(__file__), "..", "fedml_tpu")
    findings, stats = run_lint([os.path.join(pkg, "serving")],
                               rules=["lock-discipline"], extra_docs={})
    assert findings == [] and stats["suppressed"] >= 6
    findings, stats = run_lint(
        [os.path.join(pkg, "serving", "engine.py")],
        rules=["lock-discipline"], extra_docs={})
    assert findings == [] and stats["suppressed"] >= 6


def test_missing_scan_path_is_loud():
    # a typo'd CI path must not produce a vacuous "0 findings over
    # 0 files" green
    with pytest.raises(OSError, match="does not exist"):
        run_lint([os.path.join(FIXTURES, "no_such_dir")])
    from fedml_tpu.__main__ import main

    assert main(["lint", os.path.join(FIXTURES, "no_such_dir")]) == 2


def test_knob_drift_fixture():
    findings, stats = _lint_fixture("knobs", "knob-drift")
    assert len(findings) == 5 and stats["suppressed"] == 0
    msgs = [f.message for f in findings]
    assert any("`beta` is validated at config load" in m
               and "validated-then-dropped" in m for m in msgs)
    assert any("knob `delta`" in m and "does not register" in m
               for m in msgs)
    assert any("start_replica" in m and "shared knob mapping" in m
               for m in msgs)
    assert any("does not validate serve_args through serving/knobs.py" in m
               for m in msgs)
    assert any("hand-synced copy" in m for m in msgs)


def test_knob_drift_codec_leg_fixture():
    """The wire-codec half of knob-drift (ISSUE 14): a registered knob
    `make_policy` never reads, an unregistered knob it does read, a config
    that bypasses validate_comm_codec, and a resurrected hand-synced key
    list all surface. The real tree's codec plane passes via the
    zero-findings gate."""
    findings, _stats = _lint_fixture("codec_knobs", "knob-drift")
    msgs = [f.message for f in findings]
    assert len(findings) == 4, msgs
    assert any("knob `gamma`" in m and "validated-then-dropped" in m
               and "comm/codec.py CODEC_KNOBS" in m for m in msgs)
    assert any("knob `delta_knob`" in m and "does not register" in m
               for m in msgs)
    assert any("does not validate comm_codec through comm/codec.py" in m
               for m in msgs)
    assert any("hand-synced copy" in m and "CODEC_KNOBS" in m for m in msgs)


def test_knob_drift_soak_leg_fixture():
    """The live-loop soak half of knob-drift (ISSUE 15): a registered
    soak knob `soak_plan` never reads, an unregistered knob it does
    read, a config that bypasses validate_soak, and a resurrected
    hand-synced key list all surface. The real tree's soak plane passes
    via the zero-findings gate."""
    findings, _stats = _lint_fixture("soak_knobs", "knob-drift")
    msgs = [f.message for f in findings]
    assert len(findings) == 4, msgs
    assert any("knob `zipf_s`" in m and "validated-then-dropped" in m
               and "soak/knobs.py SOAK_KNOBS" in m for m in msgs)
    assert any("knob `surge_rps`" in m and "does not register" in m
               for m in msgs)
    assert any("does not validate the soak section through soak/knobs.py"
               in m for m in msgs)
    assert any("hand-synced copy" in m and "SOAK_KNOBS" in m for m in msgs)


def test_knob_drift_suppressed_and_clean():
    findings, stats = _lint_fixture("knobs_suppressed", "knob-drift")
    assert findings == [] and stats["suppressed"] == 5
    findings, stats = _lint_fixture("knobs_clean", "knob-drift")
    assert findings == [] and stats["suppressed"] == 0


def test_metric_registry_fixture():
    docs = {"FIXTURE.md": "\n".join([
        "counters: `fed.rounds_total` and the `fed.participation.*`",
        "family; trace spans: `serving.swap.fixture`.",
        "stale claim: `serving.ghost_series` was renamed away.",  # FINDING
    ])}
    findings, stats = _lint_fixture("metrics", "metric-registry",
                                    extra_docs=docs)
    by_path = {}
    for f in findings:
        by_path.setdefault(os.path.basename(f.path), set()).add(f.line)
    # typo findings anchor at the emit literals, consumer findings at the
    # miniature top / doc line
    assert by_path.pop("emit.py") == _marked_lines("metrics", "emit.py")
    assert by_path.pop("__main__.py") == _marked_lines("metrics",
                                                       "__main__.py")
    assert by_path.pop("FIXTURE.md") == {3}
    assert not by_path
    assert stats["suppressed"] == 3
    msgs = " ".join(f.message for f in findings)
    assert "one edit from the established" in msgs
    assert "no emit site produces it" in msgs


def test_metric_registry_slo_events_families():
    """The attribution plane's families (ISSUE 17): `slo.*` / `events.*`
    names are first-class to the rule — f-string prefix emits
    (`slo.burn.<name>`, `events.dropped.<track>`) satisfy prefix reads,
    a near-miss `slo.alert_total` typo and ghost consumer reads
    (`slo_budget_remaining`, `events.evicted_total`) all surface —
    while reads landing UNDER a prefix emit (`slo_burn_*`) don't."""
    findings, _stats = _lint_fixture("slo_events", "metric-registry")
    by_path = {}
    for f in findings:
        by_path.setdefault(os.path.basename(f.path), set()).add(f.line)
    assert by_path.pop("emit.py") == _marked_lines("slo_events", "emit.py")
    assert by_path.pop("__main__.py") == _marked_lines("slo_events",
                                                       "__main__.py")
    assert not by_path
    msgs = " ".join(f.message for f in findings)
    assert "slo.alert_total" in msgs and "slo.alerts_total" in msgs
    assert "slo_budget_remaining" in msgs
    assert "events.evicted_total" in msgs


def test_metric_registry_obs_fleet_families():
    """The fleet-observability families (ISSUE 18): `obs.*` names are
    first-class to the rule — prefix emits (`obs.clock_skew_ms.<a>.<b>`,
    `comm.link.<src>.<dst>.*`) satisfy prefix reads, a near-miss
    `obs.fleet.scrape_error` typo and ghost reads (`obs_fleet_lag_s` in
    a top frame, `obs.postmortem.spills` in a raw snapshot read) all
    surface."""
    findings, _stats = _lint_fixture("obs_fleet", "metric-registry")
    by_path = {}
    for f in findings:
        by_path.setdefault(os.path.basename(f.path), set()).add(f.line)
    assert by_path.pop("emit.py") == _marked_lines("obs_fleet", "emit.py")
    assert by_path.pop("__main__.py") == _marked_lines("obs_fleet",
                                                       "__main__.py")
    assert not by_path
    msgs = " ".join(f.message for f in findings)
    assert "obs.fleet.scrape_error" in msgs \
        and "obs.fleet.scrape_errors" in msgs
    assert "obs_fleet_lag_s" in msgs
    assert "obs.postmortem.spills" in msgs


def test_metric_registry_spans_do_not_satisfy_scrape_reads():
    # a span name must NOT satisfy a `top`/snapshot consumer — spans never
    # reach /metrics. The doc surface (where span names are legitimate)
    # accepts it; the scrape surface flags it.
    import tempfile

    src_emit = "def f(recorder):\n    recorder.span('serving.only_span')\n"
    src_main = ("def _top_frame(snap):\n    g = snap['gauges']\n"
                "    return g.get('serving_only_span')\n")
    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "emit.py"), "w") as f:
            f.write(src_emit)
        with open(os.path.join(d, "__main__.py"), "w") as f:
            f.write(src_main)
        findings, _ = run_lint([d], rules=["metric-registry"],
                               extra_docs={
                                   "DOC.md": "`serving.only_span` span"})
    assert len(findings) == 1
    assert findings[0].path == "__main__.py"
    assert "serving_only_span" in findings[0].message


# ------------------------------------------------- the self-enforcing gate
def test_tree_zero_findings():
    """THE gate (acceptance bar): graftlint over the whole fedml_tpu tree
    — README consumer surfaces included — reports zero findings. Every
    suppression in the tree is a reviewed, justified exception."""
    findings, stats = run_lint()
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)
    assert stats["files"] > 100    # really scanned the package
    # the engine's documented thread-ownership suppressions exist; a
    # wholesale deletion of the comments (or of the rule) would show here
    # (6 since PR 26 made _drain's three copies of one gauge update one)
    assert stats["suppressed"] >= 6


def test_rule_catalog_and_unknown_rule():
    names = [r.name for r in all_rules()]
    assert names == ["donation-after-use", "retrace-hazard", "knob-drift",
                     "metric-registry", "lock-discipline",
                     "in-trace-purity"]
    with pytest.raises(ValueError, match="unknown rule"):
        run_lint([FIXTURES], rules=["no-such-rule"])


def test_parse_error_is_a_finding_not_a_crash():
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "broken.py"), "w") as f:
            f.write("def oops(:\n")
        findings, _ = run_lint([d], extra_docs={})
    assert [f.rule for f in findings] == ["parse-error"]


# ------------------------------------------------------------ reporters/CLI
def test_reporters_schema():
    findings, stats = _lint_fixture("trace/retrace.py", "retrace-hazard")
    text = render_text(findings, stats)
    assert re.search(r"retrace\.py:\d+:\d+: retrace-hazard: ", text)
    assert "finding(s)" in text
    doc = json.loads(render_json(findings, stats))
    assert set(doc) == {"findings", "count", "files", "suppressed",
                        "rules"}
    assert doc["count"] == len(findings) == len(doc["findings"])
    assert set(doc["findings"][0]) == {"rule", "path", "line", "col",
                                       "message"}


def test_cli_lint_verb(capsys):
    from fedml_tpu.__main__ import main

    # findings -> exit 1, json schema on stdout
    rc = main(["lint", "--format", "json", "--rules", "retrace-hazard",
               os.path.join(FIXTURES, "trace", "retrace.py")])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["count"] == 3
    # clean subset -> exit 0
    rc = main(["lint", "--rules", "donation-after-use",
               os.path.join(FIXTURES, "knobs_clean")])
    assert rc == 0
    assert "0 finding(s)" in capsys.readouterr().out
    # unknown rule -> usage error, exit 2
    rc = main(["lint", "--rules", "bogus", FIXTURES])
    assert rc == 2
    # rule catalog
    rc = main(["lint", "--list-rules"])
    assert rc == 0
    assert "knob-drift" in capsys.readouterr().out


def test_diagnosis_lint_clean_probe(capsys):
    from fedml_tpu.__main__ import main

    rc = main(["diagnosis", "--only", "lint_clean"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["ok"] is True
    probe = out["checks"]["lint_clean"]
    assert probe["ok"] and probe["files"] > 100
    assert probe["scan_s"] < 20     # the CI-budget bar the probe enforces


# --------------------------------------------------------------- helpers
def test_edit_distance():
    assert edit_distance("fed.rounds_total", "fed.round_total", 1) == 1
    assert edit_distance("serving.ttft", "serving.tbt", 1) > 1
    assert edit_distance("a", "a", 1) == 0
    assert edit_distance("abc", "xyz", 1) > 1


def test_knob_registry_is_literal_and_matches_config():
    """The real registry parses as a pure literal (the import-free Docker
    hook depends on it) and config.validate really consumes it: an
    unknown knob is rejected naming the registry's key set."""
    import ast as _ast

    import fedml_tpu
    from fedml_tpu.serving.knobs import KNOBS

    src = open(os.path.join(os.path.dirname(__file__), "..", "fedml_tpu",
                            "serving", "knobs.py")).read()
    tree = _ast.parse(src)
    lit = next(n.value for n in _ast.walk(tree)
               if isinstance(n, _ast.Assign)
               and any(getattr(t, "id", None) == "KNOBS"
                       for t in n.targets))
    assert _ast.literal_eval(lit) == KNOBS
    with pytest.raises(ValueError, match="unknown serve_args knob"):
        fedml_tpu.init(config={"serve_args": {"decode_slotz": 1}})
    # the registry-driven validator still normalizes YAML-1.1 `off`
    cfg = fedml_tpu.init(config={"serve_args": {
        "decode_slots": 2, "kv_page_size": 4, "spec_decode": False}})
    assert cfg.serve_args.extra["spec_decode"] == "off"
