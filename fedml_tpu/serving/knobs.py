"""THE serve-knob registry (ISSUE 13).

One table for every `serve_args` knob: its kind/bounds, its gating
prerequisite, and WHICH surface consumes it — "predictor" knobs must be
mapped by `predictor.lm_predictor_from_serve_knobs` (the shared mapping
`start_replica` and `serving.lm_predictor_from_config` both ride),
"fleet" knobs by `scheduler.fleet_knobs`. Before this registry, the key
set lived three times (config.py's validated set, the predictor mapping,
the fleet mapping) and drifted in PRs 5, 9, and 11 — a knob validated at
load then silently dropped on the deploy path. Now config validation
iterates THIS table, and graftlint's `knob-drift` rule cross-checks the
two consumer functions against it, so a validated-but-unmapped knob
fails lint instead of a review pass.

`KNOBS` stays a PURE LITERAL: graftlint reads it with
`ast.literal_eval`, so the linter never has to import this package (the
Docker build hook lints before any jax wheel exists). This module must
also stay import-light itself — config.py pulls it in at load time and
config load is deliberately jax-free.
"""
from __future__ import annotations

# knob -> spec. Kinds: "int" (min), "num" (strict: >0 vs >=0), "bool",
# "choice" (choices). "requires" names the gating knob whose absence makes
# this one silently dead (refused at config load); "off" is the value that
# leaves the knob off, so naming it asks for nothing. "consumer" names the
# mapping that must read the knob: "predictor" =
# predictor.lm_predictor_from_serve_knobs, "fleet" =
# scheduler.fleet_knobs.
KNOBS = {
    "decode_slots":       {"kind": "int", "min": 0,
                           "consumer": "predictor"},
    "engine_max_len":     {"kind": "int", "min": 1,
                           "consumer": "predictor"},
    "engine_fetch_chunk": {"kind": "int", "min": 1,
                           "consumer": "predictor"},
    "engine_eos_id":      {"kind": "int", "min": 0,
                           "consumer": "predictor"},
    "sampler_cache_size": {"kind": "int", "min": 1,
                           "consumer": "predictor"},
    "kv_cache":           {"kind": "bool", "consumer": "predictor"},
    "engine_mp":          {"kind": "int", "min": 1, "off": 1,
                           "consumer": "predictor",
                           "requires": "decode_slots"},
    "kv_page_size":       {"kind": "int", "min": 1,
                           "consumer": "predictor",
                           "requires": "decode_slots"},
    "kv_n_pages":         {"kind": "int", "min": 2,
                           "consumer": "predictor",
                           "requires": "decode_slots"},
    "prefill_chunk":      {"kind": "int", "min": 0,
                           "consumer": "predictor",
                           "requires": "decode_slots"},
    "prefix_cache":       {"kind": "bool", "consumer": "predictor",
                           "requires": "decode_slots"},
    "paged_kernel":       {"kind": "bool", "off": False,
                           "consumer": "predictor",
                           "requires": "decode_slots"},
    "spec_decode":        {"kind": "choice", "choices": ["off", "ngram"],
                           "off": "off", "consumer": "predictor",
                           "requires": "decode_slots"},
    "spec_k":             {"kind": "int", "min": 1,
                           "consumer": "predictor",
                           "requires": "spec_decode"},
    "kv_quant":           {"kind": "choice", "choices": ["off", "int8"],
                           "off": "off", "consumer": "predictor",
                           "requires": "decode_slots"},
    "admit_batch":        {"kind": "int", "min": 1, "off": 1,
                           "consumer": "predictor",
                           "requires": "decode_slots"},
    "drain_timeout_s":    {"kind": "num", "strict": False,
                           "consumer": "predictor"},
    "affinity_routing":   {"kind": "bool", "consumer": "fleet",
                           "requires": "prefix_cache"},
    "shed_watermark":     {"kind": "num", "strict": False,
                           "consumer": "fleet"},
    "retry_after_s":      {"kind": "num", "strict": True,
                           "consumer": "fleet"},
    "probation_deadline_s": {"kind": "num", "strict": True,
                             "consumer": "fleet"},
    "probe_backoff_s":    {"kind": "num", "strict": True,
                           "consumer": "fleet"},
}


def knob_names() -> set[str]:
    return set(KNOBS)


def consumer_knobs(consumer: str) -> set[str]:
    """Knob names owned by one consumer surface ("predictor"/"fleet")."""
    return {k for k, spec in KNOBS.items() if spec["consumer"] == consumer}


def validate_serve_args(extra: dict) -> None:
    """Validate (and normalize, in place) a `serve_args` knob dict.

    Moved here from config.Config.validate so the key set, kinds, and
    gating live NEXT TO the registry they iterate — config.py calls this
    at load time and cannot drift from the consumer surfaces. Raises
    ValueError with the exact messages the config tests pin.

    serve_args is fully owned by this framework (no reference-YAML
    grab-bag to stay compatible with), so UNKNOWN keys are rejected too —
    a misspelled decode_slots must not pass silently.
    """
    unknown = set(extra) - set(KNOBS)
    if unknown:
        raise ValueError(
            f"unknown serve_args knob(s) {sorted(unknown)}; valid: "
            f"{sorted(KNOBS)}")
    for knob, spec in KNOBS.items():
        val = extra.get(knob)
        if val is None:
            continue
        if spec["kind"] == "bool":
            if not isinstance(val, bool):
                raise ValueError(
                    f"serve_args.{knob} must be a boolean; got {val!r}")
        elif spec["kind"] == "int":
            lo = spec["min"]
            try:
                ok = (not isinstance(val, bool)
                      and int(val) == float(val) and int(val) >= lo)
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    f"serve_args.{knob} must be an integer >= {lo}; "
                    f"got {val!r}")
        elif spec["kind"] == "num":
            strict = spec["strict"]
            try:
                ok = (not isinstance(val, bool)
                      and (float(val) > 0 if strict else float(val) >= 0))
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    f"serve_args.{knob} must be a "
                    f"{'positive' if strict else 'non-negative'} number; "
                    f"got {val!r}")
    sd = extra.get("spec_decode")
    if sd is not None:
        # YAML 1.1 reads an unquoted `off` as boolean False — that IS
        # the documented disable spelling, so normalize it instead of
        # rejecting the user's own docs back at them (True has no
        # mode to normalize to: name the quoting problem)
        if sd is False:
            sd = extra["spec_decode"] = "off"
        if sd is True:
            raise ValueError(
                "serve_args.spec_decode: true is not a mode — use "
                "'ngram' (YAML parses unquoted off/on as booleans; "
                "quote the value)")
        if sd not in KNOBS["spec_decode"]["choices"]:
            raise ValueError(
                "serve_args.spec_decode must be 'off' or 'ngram'; "
                f"got {sd!r}")
    if extra.get("spec_k") is not None and sd in (None, "off"):
        raise ValueError(
            "serve_args.spec_k requires spec_decode: ngram — "
            "the draft length only exists under speculation; "
            "without it the knob would be silently ignored")
    kq = extra.get("kv_quant")
    if kq is not None:
        # YAML 1.1 reads unquoted `off` as False — the documented
        # disable spelling, same normalization as spec_decode
        if kq is False:
            kq = extra["kv_quant"] = "off"
        if kq is True:
            raise ValueError(
                "serve_args.kv_quant: true is not a mode — use 'int8' "
                "(YAML parses unquoted off/on as booleans; quote the "
                "value)")
        if kq not in KNOBS["kv_quant"]["choices"]:
            raise ValueError(
                f"serve_args.kv_quant must be 'off' or 'int8'; got {kq!r}")
    # every knob of the decode engine (the page pool, the kernel,
    # speculation, int8 pages, batched admission, engine_mp) only takes
    # effect inside it: a config naming one without decode_slots would
    # silently serve per-request — refuse at load instead (the other
    # engine_* knobs double as per-request knobs, e.g. engine_max_len
    # sizes both paths, so they are not gated)
    if not extra.get("decode_slots"):
        for knob, spec in KNOBS.items():
            val = extra.get(knob)
            if spec.get("requires") != "decode_slots" or val is None:
                continue
            if spec["kind"] == "int":
                val = int(val)
            if "off" not in spec or val != spec["off"]:
                raise ValueError(
                    f"serve_args.{knob} requires decode_slots > 0 — the "
                    "knob configures the decode engine (serving/engine.py); "
                    "without slots it would be silently ignored")
    if extra.get("affinity_routing"):
        if not extra.get("decode_slots") \
                or extra.get("prefix_cache") is False:
            raise ValueError(
                "serve_args.affinity_routing requires the engine prefix "
                "cache (decode_slots > 0, prefix_cache not disabled) — "
                "affinity routes requests to the replica whose cache "
                "already holds their prefix; without one the knob would "
                "be silently ignored")
