"""CLI — `python -m fedml_tpu <cmd>`.

(reference: python/fedml/cli/cli.py:18-76 — click commands `fedml version /
env / run / launch / build / logs / diagnosis / ...`. The SaaS-bound legs
(login, OTA) have no meaning without a cloud; everything else has a
local-first analog here:
  version/env  — runtime report
  run          — config-driven run (fedml_config.yaml accepted unchanged)
  launch       — submit a job spec through the scheduler tier
                 (MasterAgent + WorkerAgent + optional sqlite store)
  build        — package a job directory into a distributable tarball
                 (reference: cli/build: client/server package builder)
  logs         — tail per-run logs/events written by the mlops facade
  diagnosis    — transport + device connectivity checks (reference:
                 slave/client_diagnosis.py MQTT/S3 probes)
  bench        — run the repo benchmark)
"""
from __future__ import annotations

import argparse
import json
import sys


def cmd_version(_args) -> int:
    from . import __version__

    print(f"fedml_tpu {__version__}")
    return 0


def cmd_env(_args) -> int:
    """Environment report (reference: `fedml env`,
    computing/scheduler/env/collect_env.py)."""
    import platform

    info = {"python": sys.version.split()[0],
            "platform": platform.platform()}
    try:
        import jax

        info["jax"] = jax.__version__
        info["devices"] = [str(d) for d in jax.devices()]
        info["default_backend"] = jax.default_backend()
    except Exception as e:  # pragma: no cover
        info["jax_error"] = str(e)
    for mod in ("flax", "optax", "orbax.checkpoint", "numpy"):
        try:
            import importlib

            m = importlib.import_module(mod)
            info[mod] = getattr(m, "__version__", "?")
        except Exception:
            info[mod] = None
    print(json.dumps(info, indent=2))
    return 0


def cmd_run(args) -> int:
    """Config-driven run (reference: `fedml run` on a fedml_config.yaml).
    training_type selects the runtime via FedMLRunner."""
    import fedml_tpu
    from .config import (
        TRAINING_TYPE_CENTRALIZED, TRAINING_TYPE_SIMULATION,
    )
    from .runner import FedMLRunner

    cfg = fedml_tpu.init(config_path=args.config)
    if args.rounds is not None:
        cfg.train_args.comm_round = args.rounds
    tt = cfg.common_args.training_type
    if tt == TRAINING_TYPE_SIMULATION:
        hist = fedml_tpu.run_simulation(cfg)
        print(json.dumps(hist[-1]))
        return 0
    if tt == TRAINING_TYPE_CENTRALIZED:
        runner = FedMLRunner(cfg)
        hist = runner.run()
        print(json.dumps(hist[-1]))
        return 0
    # cross_silo / cross_device need model + per-role dataset wiring the
    # YAML alone can't express — those run through the python API
    print(f"training_type={tt!r} requires the python API "
          "(fedml_tpu.FedMLRunner with model/dataset/input_shape); the CLI "
          "runs simulation and centralized configs", file=sys.stderr)
    return 2


def cmd_bench(_args) -> int:
    # the child owns the chip: this parent must never have touched jax
    # (`import fedml_tpu` and this module stay jax-free — pinned in
    # tests/test_cli_platform.py)
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.call([sys.executable, os.path.join(root, "bench.py")])


def cmd_launch(args) -> int:
    """Submit a job spec through the scheduler tier (reference: `fedml
    launch job.yaml` submits to the Launch platform; here the MasterAgent is
    local-first — loopback by default, and durable when --store is given).
    The job yaml/json is a scheduler spec: {"type": "simulation"|"python"|
    "serve", ..., "requirements": {...}}."""
    import uuid

    import yaml

    from .comm import FedCommManager
    from .comm.loopback import LoopbackTransport, release_router
    from .scheduler import MasterAgent, WorkerAgent

    with open(args.job) as f:
        spec = yaml.safe_load(f)
    run_id = f"launch-{uuid.uuid4().hex[:6]}"
    master = MasterAgent(FedCommManager(LoopbackTransport(0, run_id), 0),
                         store_path=args.store)
    worker = WorkerAgent(FedCommManager(LoopbackTransport(1, run_id), 1), 1)
    master.run()
    worker.run()
    worker.announce()
    jid = master.submit(spec)
    job = master.wait(jid, timeout=args.timeout)
    print(json.dumps({"job_id": jid, "status": job.status,
                      "result": _jsonable(job.result)}))
    master.stop()
    worker.stop()
    release_router(run_id)
    return 0 if job.status == "FINISHED" else 1


def _jsonable(x):
    try:
        json.dumps(x)
        return x
    except (TypeError, ValueError):
        return repr(x)


def cmd_build(args) -> int:
    """Package a job directory into a distributable tarball with a manifest
    (reference: cli/cli.py `fedml build` — client/server package builder;
    the package here is source + entry + sha256 manifest, consumable by
    `launch` on any host with fedml_tpu installed)."""
    import hashlib
    import os
    import tarfile
    import time

    src = os.path.abspath(args.source)
    if not os.path.isdir(src):
        print(f"source dir not found: {src}", file=sys.stderr)
        return 1
    entry = args.entry
    if entry and not os.path.exists(os.path.join(src, entry)):
        print(f"entry {entry!r} not found under {src}", file=sys.stderr)
        return 1
    name = args.name or os.path.basename(src.rstrip("/"))
    os.makedirs(args.dest, exist_ok=True)
    out = os.path.join(args.dest, f"{name}.tar.gz")
    manifest = {"name": name, "entry": entry, "created": time.time(),
                "files": {}}
    for root, _dirs, files in os.walk(src):
        for fn in sorted(files):
            p = os.path.join(root, fn)
            rel = os.path.relpath(p, src)
            if rel == "fedml_manifest.json":
                continue  # superseded by the generated manifest below
            with open(p, "rb") as f:
                manifest["files"][rel] = hashlib.sha256(f.read()).hexdigest()
    # the manifest goes into the tarball from memory (never written into the
    # user's source dir); a pre-existing fedml_manifest.json — e.g. from an
    # unpacked previous package — is excluded so the archive holds exactly
    # one, self-consistent manifest member
    import io

    man_bytes = json.dumps(manifest, indent=2).encode()
    with tarfile.open(out, "w:gz") as tar:
        tar.add(src, arcname=name,
                filter=lambda ti: None
                if ti.name == f"{name}/fedml_manifest.json" else ti)
        info = tarfile.TarInfo(f"{name}/fedml_manifest.json")
        info.size = len(man_bytes)
        info.mtime = int(manifest["created"])
        tar.addfile(info, io.BytesIO(man_bytes))
    print(json.dumps({"package": out, "files": len(manifest["files"]),
                      "entry": entry}))
    return 0


def cmd_logs(args) -> int:
    """Print per-run logs/events the mlops facade wrote (reference: `fedml
    logs` pulls run logs; local-first: they're already on disk under
    tracking_args.log_file_dir)."""
    import os

    d = args.log_dir
    if not os.path.isdir(d):
        print(f"no log dir {d!r}", file=sys.stderr)
        return 1
    names = sorted(os.listdir(d))
    if args.run is not None:
        names = [n for n in names if n.startswith(args.run)]
    if args.list or not names:
        print(json.dumps({"log_dir": d, "runs": names}))
        return 0
    for n in names:
        p = os.path.join(d, n)
        if not os.path.isfile(p):
            continue
        with open(p) as f:
            lines = f.readlines()
        for line in lines[-args.tail:]:
            sys.stdout.write(f"[{n}] {line}")
    return 0


def _newest_events_file(log_dir: str, run) -> str:
    """The newest `<run>.events.jsonl` under `log_dir` (optionally filtered
    by run-name prefix) — shared by the `report` and `top` verbs."""
    import os

    if not os.path.isdir(log_dir):
        raise FileNotFoundError(f"no log dir {log_dir!r}")
    names = sorted(n for n in os.listdir(log_dir)
                   if n.endswith(".events.jsonl")
                   and (run is None or n.startswith(run)))
    if not names:
        raise FileNotFoundError(
            f"no *.events.jsonl under {log_dir!r}"
            + (f" matching {run!r}" if run else ""))
    # newest run wins when several match
    return max((os.path.join(log_dir, n) for n in names),
               key=os.path.getmtime)


def cmd_report(args) -> int:
    """Telemetry report for a tracked run (reference: the MLOps run page;
    local-first: everything is already on disk). Reads the run's
    events JSONL (utils/sinks.JsonlSink) and prints a text summary —
    per-span durations, the round-time budget table (transport share by
    backend — ISSUE 17's attribution plane), SLO alert totals, metric-row
    counts, and the end-of-run counters/histograms snapshot that
    mlops.finish appended — plus pointers to the Chrome-trace artifact
    when present. `--format json` emits the same facts as one stable
    machine-readable object (schema key pins the shape); exit codes are
    identical in both formats. `--merge run_dirA run_dirB ...` switches to
    trace federation (ISSUE 18): N processes' Chrome traces folded into one
    clock-corrected Perfetto timeline. `--fleet URL` folds a live
    FleetCollector's snapshot (per-process columns, fleet sums, staleness
    marks) into the report."""
    import os

    if getattr(args, "merge", None):
        return _report_merge(args)

    fleet = None
    if getattr(args, "fleet", None):
        try:
            fleet = _fetch_fleet(args.fleet)
        except Exception as e:  # noqa: BLE001 — operator-facing CLI
            print(f"fleet fetch failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 1

    path = args.events
    if path is None:
        try:
            path = _newest_events_file(args.log_dir, args.run)
        except FileNotFoundError as e:
            if fleet is not None:
                # fleet-only report: a live fleet needs no local run dir
                if getattr(args, "format", "text") == "json":
                    print(json.dumps({"schema": 2, "fleet": fleet},
                                     indent=2, sort_keys=True))
                else:
                    print(_render_fleet(fleet))
                return 0
            print(str(e), file=sys.stderr)
            return 1

    spans: dict = {}
    span_rows: list = []
    n_metrics = n_sysperf = 0
    report_row = None
    with open(path) as f:
        for line in f:
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if row.get("kind") == "span":
                agg = spans.setdefault(row.get("name", "?"),
                                       {"count": 0, "total_s": 0.0})
                agg["count"] += 1
                agg["total_s"] += float(row.get("duration", 0.0))
                span_rows.append(row)
            elif row.get("kind") == "metrics":
                n_metrics += 1
                if "sysperf" in row:
                    n_sysperf += 1
                if "report" in row:
                    report_row = row["report"]

    if not spans and n_metrics == 0:
        # a run dir with an events file but zero telemetry rows used to fall
        # through to an empty report — fail loudly instead (ISSUE 3)
        print(f"no telemetry rows in {path} — the run wrote no spans or "
              "metrics (did it crash before the first round, or run with "
              "tracking disabled?)", file=sys.stderr)
        return 1

    from .utils.attribution import attribute, link_table, \
        render_link_table, render_table, rows_from_payloads
    from .utils.postmortem import load_postmortem

    att = attribute(rows_from_payloads(span_rows))
    snap = (report_row or {}).get("metrics", {})
    counters = snap.get("counters", {})
    hists = snap.get("histograms", {})
    gauges = snap.get("gauges", {})
    dropped_total = int(counters.get("events.dropped_total", 0))
    raw = sum(v for k, v in counters.items()
              if k.startswith("comm.codec.") and k.endswith(".bytes_raw"))
    wire = sum(v for k, v in counters.items()
               if k.startswith("comm.codec.") and k.endswith(".bytes_wire"))
    lg_req = counters.get("loadgen.requests", 0)
    alerts_total = int(counters.get("slo.alerts_total", 0))
    alerts = {k[len("slo.alerts."):]: int(v) for k, v in counters.items()
              if k.startswith("slo.alerts.")}
    burns = {k[len("slo.burn."):]: v for k, v in gauges.items()
             if k.startswith("slo.burn.")}
    trace = path.replace(".events.jsonl", ".trace.json")
    links = link_table(att, snapshot=snap if report_row else None)
    # flight recorder (ISSUE 18): a crashed/SIGKILLed process leaves
    # <run_dir>/postmortem.json next to its events file
    pm = load_postmortem(os.path.dirname(os.path.abspath(path)))

    if getattr(args, "format", "text") == "json":
        out = {
            # schema 2 (ISSUE 18): ADDITIVE only — every schema-1 key is
            # still present with its schema-1 shape; "links",
            # "postmortem", and "fleet" are the new keys
            "schema": 2,
            "links": links,
            "postmortem": pm,
            "fleet": fleet,
            "events_path": path,
            "trace_path": trace if os.path.exists(trace) else None,
            "metric_rows": n_metrics,
            "sysperf_rows": n_sysperf,
            "spans": spans,
            "budget": att,
            "slo": {"alerts_total": alerts_total, "alerts": alerts,
                    "burn": burns},
            "dropped_spans_total": dropped_total,
            "headline": {
                "wire_codec_reduction": (raw / wire) if raw and wire
                else None,
                "loadgen_requests": int(lg_req) if lg_req else None,
            },
            "metrics": snap if report_row else None,
        }
        print(json.dumps(out, indent=2, sort_keys=True))
        return 0

    print(f"run events: {path}")
    if dropped_total:
        # trace-loss visibility (ISSUE 17): a ring past its cap silently
        # read as a short run before — now it reads as a truncated one
        print(f"WARNING: trace TRUNCATED — {dropped_total} span/metric "
              "rows dropped past the in-memory ring cap "
              "(FEDML_TPU_EVENTS_CAP); the events JSONL keeps every row, "
              "but the exported Chrome trace is missing the oldest spans",
              file=sys.stderr)
    if os.path.exists(trace):
        print(f"chrome trace: {trace}  (open at ui.perfetto.dev)")
    if pm is not None and pm.get("reason") != "finish":
        import time as _time

        died = _time.strftime("%Y-%m-%d %H:%M:%S",
                              _time.localtime(pm.get("t", 0)))
        print(f"POSTMORTEM: process {pm.get('process')!r} died at {died} "
              f"({pm.get('reason')}); last span was "
              f"{pm.get('last_span')!r} — {len(pm.get('spans') or [])} "
              f"spans, {len(pm.get('frames') or [])} comm frames in "
              + os.path.join(os.path.dirname(os.path.abspath(path)),
                             "postmortem.json"))
    print(f"metric rows: {n_metrics} ({n_sysperf} sysperf)")
    if spans:
        print("spans:")
        width = max(len(n) for n in spans)
        for name, agg in sorted(spans.items(),
                                key=lambda kv: -kv[1]["total_s"]):
            avg_ms = agg["total_s"] / agg["count"] * 1e3
            print(f"  {name:<{width}}  count={agg['count']:<8d} "
                  f"total={agg['total_s']:.3f}s  avg={avg_ms:.2f}ms")
    if att.get("totals"):
        print(render_table(att))
    if links:
        print(render_link_table(att, snapshot=snap if report_row else None))
    if fleet is not None:
        print(_render_fleet(fleet))
    if report_row:
        # wire codec plane (ISSUE 14): surface the payload-compression
        # ratio directly — summed over backends from the sender-side
        # `comm.codec.` byte counters
        if raw and wire:
            print(f"wire codec: {raw / wire:.1f}x payload reduction "
                  f"({_fmt_bytes(raw)} raw -> {_fmt_bytes(wire)} wire)")
        # live-loop soak (ISSUE 15): the closed-loop ledger — published
        # training rounds vs the loadgen's status taxonomy
        if lg_req:
            print(f"live loop: {int(lg_req)} requests — "
                  f"ok {int(counters.get('loadgen.ok', 0))}, "
                  f"shed {int(counters.get('loadgen.shed', 0))}, "
                  f"err {int(counters.get('loadgen.errors', 0))}; "
                  f"{int(counters.get('soak.publishes', 0))} rounds "
                  "published to serving")
        if alerts_total:
            worst = max(burns.items(), key=lambda kv: kv[1],
                        default=(None, 0.0))
            print(f"slo alerts: {alerts_total} fired ("
                  + ", ".join(f"{k} x{v}" for k, v in sorted(alerts.items()))
                  + (f"); worst burn {worst[0]} {worst[1]:.1f}x"
                     if worst[0] else ")"))
        if counters:
            print("counters:")
            for k in sorted(counters):
                print(f"  {k} = {counters[k]}")
        if hists:
            print("histograms:")
            for k in sorted(hists):
                h = hists[k]
                print(f"  {k}  count={h.get('count')} "
                      f"p50={h.get('p50')} p99={h.get('p99')} "
                      f"max={h.get('max')}")
        if gauges:
            print("gauges:")
            for k in sorted(gauges):
                print(f"  {k} = {gauges[k]}")
    else:
        print("(no end-of-run metrics snapshot row — run finished without "
              "mlops.finish, or predates the telemetry layer)")
    return 0


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}TB"


def _report_merge(args) -> int:
    """`report --merge dirA dirB ...`: fold N run dirs' (or trace files')
    Chrome traces into ONE clock-corrected Perfetto timeline with a flow
    arrow per cross-process send→handle pair (utils/obsfleet.merge_traces).
    Exit 1 if the corrected timeline still shows a recv before its send —
    that invariant is the whole point of the correction."""
    import os

    from .utils.obsfleet import (load_trace, merge_traces,
                                 verify_merged_order)

    inputs = []
    for spec in args.merge:
        if os.path.isfile(spec):
            path = spec
            name = os.path.basename(spec).split(".")[0] or spec
        elif os.path.isdir(spec):
            names = [n for n in os.listdir(spec)
                     if n.endswith(".trace.json")]
            if not names:
                print(f"--merge: no *.trace.json under {spec!r}",
                      file=sys.stderr)
                return 1
            path = max((os.path.join(spec, n) for n in names),
                       key=os.path.getmtime)
            name = os.path.basename(os.path.normpath(spec))
        else:
            print(f"--merge: {spec!r} is neither a trace file nor a run "
                  "dir", file=sys.stderr)
            return 1
        inputs.append((name, path))
    # duplicate lane names would fold two processes into one pid label
    counts: dict = {}
    uniq = []
    for name, path in inputs:
        n = counts.get(name, 0)
        counts[name] = n + 1
        uniq.append((f"{name}#{n}" if n else name, path))
    out_path = args.out or "merged.trace.json"
    res = merge_traces(uniq, out_path=out_path)
    bad = verify_merged_order(load_trace(out_path))
    if getattr(args, "format", "text") == "json":
        print(json.dumps(
            {**{k: v for k, v in res.items() if k != "trace"},
             "order_violations": bad}, indent=2, sort_keys=True))
        return 0 if bad == 0 else 1
    print(f"merged trace: {out_path}  (open at ui.perfetto.dev)")
    print(f"processes: {len(res['processes'])} "
          f"({', '.join(res['processes'])})  events: {res['events']}  "
          f"send->handle pairs: {res['pairs']}  "
          f"stitched flows: {res['flows']}")
    if res["clock_skew_ms"]:
        print("clock skew: " + "  ".join(
            f"{k} {v:+.3f}ms"
            for k, v in sorted(res["clock_skew_ms"].items())))
    if res["clamped"]:
        print(f"clamped events: {res['clamped']} (pair constraints "
              "infeasible — ordering invariant enforced per event)")
    if bad:
        print(f"ERROR: {bad} flow(s) still show recv before the "
              "corrected send", file=sys.stderr)
        return 1
    return 0


def _fetch_fleet(spec: str) -> dict:
    """Fleet snapshot from a FleetCollector: a base URL (its /fleet JSON
    endpoint — a .../metrics URL is rewritten), or a local JSON file a
    collector's snapshot was saved to."""
    import os

    if os.path.isfile(spec):
        with open(spec) as f:
            return json.load(f)
    import urllib.request

    url = spec
    if url.endswith("/metrics"):
        url = url[:-len("/metrics")] + "/fleet"
    elif not url.endswith("/fleet"):
        url = url.rstrip("/") + "/fleet"
    with urllib.request.urlopen(url, timeout=5) as r:
        return json.loads(r.read().decode())


def _render_fleet(fs: dict) -> str:
    """Per-process columns + a fleet-sums column from a FleetCollector
    snapshot ({"processes": ..., "sums": ...}); stale processes are
    starred in the header and called out on the status line."""
    procs = fs.get("processes") or {}
    sums = fs.get("sums") or {}
    names = sorted(procs)

    def fmt(v):
        return "-" if v is None else f"{v:g}"

    def cell(snap, kind, key):
        if not snap:
            return "-"
        v = (snap.get(kind) or {}).get(key)
        if v is not None and kind == "histograms":
            v = v.get("count", 0)
        return fmt(v)

    rows = []
    for kind, suffix in (("counters", ""), ("gauges", ""),
                         ("histograms", " (count)")):
        keys = set(sums.get(kind) or {})
        for p in procs.values():
            keys |= set(((p.get("snapshot") or {}).get(kind)) or {})
        for k in sorted(keys):
            sv = (sums.get(kind) or {}).get(k)
            if sv is not None and kind == "histograms":
                sv = sv.get("count", 0)
            rows.append(
                [k + suffix]
                + [cell((procs[n].get("snapshot")), kind, k)
                   for n in names] + [fmt(sv)])
    head = (["metric"]
            + [n + ("*" if procs[n].get("stale") else "") for n in names]
            + ["fleet"])
    widths = [max(len(str(r[i])) for r in [head] + rows)
              for i in range(len(head))]
    status = []
    for n in names:
        p = procs[n]
        s = f"{n}=" + ("STALE" if p.get("stale") else "ok")
        if p.get("age_s") is not None:
            s += f" ({p['age_s']:.1f}s ago)"
        if p.get("error"):
            s += f" [{p['error'][:60]}]"
        status.append(s)
    lines = ["fleet: " + ", ".join(status)
             + "   (* = stale: last scrape failed or too old)"]
    lines.append("  ".join(h.ljust(w) for h, w in zip(head, widths)))
    for r in rows:
        lines.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(lines)


def _top_frame(snap: dict, source: str, prev: dict = None,
               dt: float = None) -> str:
    """One screen of run health from a parsed /metrics snapshot (sanitized
    Prometheus names). `prev`+`dt` turn cumulative counters into live
    rates."""
    import time as _time

    from .utils.prometheus import histogram_percentile

    c, g, h = snap["counters"], snap["gauges"], snap["histograms"]

    def rate(key):
        if prev is None or not dt:
            return None
        return (c.get(key, 0) - prev["counters"].get(key, 0)) / dt

    lines = [f"fedml_tpu top — {source}  "
             f"({_time.strftime('%Y-%m-%d %H:%M:%S')})"]
    rnd = g.get("fed_round")
    row = [f"round {int(rnd)}" if rnd is not None else "round -",
           f"rounds_total {int(c.get('fed_rounds_total', 0))}"]
    rr = rate("fed_rounds_total")
    if rr is not None:
        row.append(f"rounds/s {rr:.2f}")
    if "fed_health_round_s" in g:
        row.append(f"last_round {g['fed_health_round_s'] * 1e3:.1f}ms")
    if "fed_version" in g:
        row.append(f"async_version {int(g['fed_version'])}")
    lines.append("  ".join(row))

    # ------------------------------------------------------------- health
    lines.append(
        "health: divergent_now {}  flags_total {}  straggler_rounds {}  "
        "norm_median {:.4g}  cosine_min {:.3f}".format(
            int(g.get("fed_health_divergent", 0)),
            int(c.get("fed_health_flags_total", 0)),
            int(c.get("fed_health_straggler_rounds_total", 0)),
            g.get("fed_health_update_norm_median", float("nan")),
            g.get("fed_health_cosine_min", float("nan"))))
    flags = {k[len("fed_health_flags_c"):-len("_total")]: int(v)
             for k, v in c.items()
             if k.startswith("fed_health_flags_c") and k.endswith("_total")}
    lines.append("flags: " + (" ".join(
        f"c{cid}x{n}" for cid, n in sorted(
            flags.items(), key=lambda kv: -kv[1])[:12]) or "none"))

    # -------------------------------------------------------- participation
    part = {k[len("fed_participation_c"):-len("_total")]: int(v)
            for k, v in c.items()
            if k.startswith("fed_participation_c") and k.endswith("_total")}
    if part:
        top = sorted(part.items(), key=lambda kv: (-kv[1], int(kv[0])))[:10]
        lines.append(
            f"participation: {len(part)} clients seen | top "
            + " ".join(f"c{cid}:{n}" for cid, n in top))
    else:
        lines.append("participation: (none yet)")

    # ------------------------------------------------------------ staleness
    st = h.get("fed_staleness")
    if st and st["count"]:
        p50 = histogram_percentile(st["buckets"], 0.5)
        p99 = histogram_percentile(st["buckets"], 0.99)
        lines.append(
            f"staleness: n={st['count']} mean={st['sum'] / st['count']:.2f} "
            f"p50<={p50:g} p99<={p99:g}")

    # --------------------------------------------- chunked-cohort ingest
    # (ISSUE 8: cohort_chunk streaming — simulation/ingest.py)
    if c.get("fed_ingest_chunks_total"):
        n_ch = int(c["fed_ingest_chunks_total"])
        seg = (f"ingest: chunks {n_ch}  "
               f"{_fmt_bytes(c.get('fed_ingest_bytes_total', 0))}  "
               f"prefetched {int(c.get('fed_ingest_prefetched_total', 0))}"
               f"/{n_ch}")
        ph = h.get("fed_ingest_put_s")
        if ph and ph["count"]:
            p50 = histogram_percentile(ph["buckets"], 0.5)
            if p50 is not None:
                seg += f"  put_p50<={p50 * 1e3:.2f}ms"
        br = rate("fed_ingest_bytes_total")
        if br is not None:
            seg += f"  {_fmt_bytes(br)}/s"
        lines.append(seg)
    # cost model renders on its own: it runs without chunking too (async
    # loop, mesh-less sync sim — both record and refresh the gauges)
    if "fed_cost_model_fit_error" in g:
        err = g["fed_cost_model_fit_error"]
        lines.append(
            "cost_model: "
            + ("ENGAGED" if g.get("fed_cost_model_engaged") else "warming")
            + (f"  fit_err {err:.2f}" if err >= 0 else "  fit_err inf")
            + f"  dispatches {int(c.get('fed_cost_model_dispatches_total', 0))}")

    # --------------------------------------------- cross-silo durability
    # (ISSUE 10: server resume / liveness eviction / rejoin / fencing)
    if "fed_server_clients_online" in g or c.get("fed_server_resumes_total") \
            or c.get("fed_server_checkpoints_total"):
        seg = (f"silo: online {int(g.get('fed_server_clients_online', 0))}"
               f"/{int(g.get('fed_server_clients_total', 0))}"
               f"  gen {int(g.get('fed_server_generation', 0))}")
        for label, key in (("resumes", "fed_server_resumes_total"),
                           ("ckpts", "fed_server_checkpoints_total"),
                           ("evicted", "fed_server_evicted_total"),
                           ("rejoins", "fed_server_rejoins_total"),
                           ("stale_gen",
                            "fed_server_stale_gen_rejected_total"),
                           ("quorum_fail",
                            "fed_server_quorum_unreachable_total"),
                           ("reattach", "fed_client_reattaches_total")):
            v = int(c.get(key, 0))
            if v:
                seg += f"  {label} {v}"
        lines.append(seg)

    # ----------------------------------------------------------------- comm
    backends = sorted({k.split("_")[1] for k in c
                       if k.startswith("comm_") and "_bytes_" in k
                       and not k.startswith("comm_codec_")})
    for b in backends:
        tx = c.get(f"comm_{b}_bytes_sent_total", 0)
        rx = c.get(f"comm_{b}_bytes_recv_total", 0)
        seg = f"comm[{b}]: tx {_fmt_bytes(tx)}  rx {_fmt_bytes(rx)}"
        txr = rate(f"comm_{b}_bytes_sent_total")
        if txr is not None:
            seg += f"  tx/s {_fmt_bytes(txr)}"
        rxr = rate(f"comm_{b}_bytes_recv_total")
        if rxr is not None:
            seg += f"  rx/s {_fmt_bytes(rxr)}"
        # wire codec plane (ISSUE 14): sender-side payload accounting —
        # raw dense bytes vs what actually hit the wire for codec-handled
        # training payloads on this backend
        raw = c.get(f"comm_codec_{b}_bytes_raw_total", 0)
        wire = c.get(f"comm_codec_{b}_bytes_wire_total", 0)
        if raw and wire:
            seg += (f"  codec {raw / wire:.1f}x "
                    f"({_fmt_bytes(wire)} wire)")
        lines.append(seg)

    # -------------------------------------------------------------- serving
    if "serving_requests_total" in c or "serving_tokens_total" in c:
        seg = (f"serving: requests {int(c.get('serving_requests_total', 0))}"
               f"  errors {int(c.get('serving_errors_total', 0))}  "
               f"queue {int(g.get('serving_queue_depth', 0))}")
        sh = h.get("serving_request_s")
        if sh and sh["count"]:
            p50 = histogram_percentile(sh["buckets"], 0.5)
            if p50 is not None:
                seg += f"  p50<={p50 * 1e3:.2f}ms"
        lines.append(seg)
        # fleet-control plane (ISSUE 9): replica pool health, model
        # versions across the rolling updater, load sheds, streaming
        if ("serving_replicas_ready" in g or "serving_model_version" in g
                or c.get("serving_shed_total")):
            seg = (f"fleet: ready {int(g.get('serving_replicas_ready', 0))}"
                   f"  suspect "
                   f"{int(g.get('serving_replicas_suspect', 0))}")
            ver = g.get("serving_fleet_version",
                        g.get("serving_model_version"))
            if ver is not None:
                seg += f"  version {int(ver)}"
            seg += f"  shed {int(c.get('serving_shed_total', 0))}"
            sr = rate("serving_shed_total")
            if sr is not None:
                seg += f"  shed/s {sr:.1f}"
            rec = int(c.get("serving_replica_recoveries_total", 0))
            if rec:
                seg += f"  recovered {rec}"
            fo = int(c.get("serving_stream_failovers_total", 0))
            if fo:
                seg += f"  stream_failovers {fo}"
            # prefix-affinity routing (ISSUE 16): share of requests
            # whose first placement landed on a replica already holding
            # their prefix page — the fleet-wide cache-locality signal
            ah = int(c.get("serving_affinity_hits_total", 0))
            am = int(c.get("serving_affinity_misses_total", 0))
            af = int(c.get("serving_affinity_fallbacks_total", 0))
            if ah + am + af:
                seg += f"  affinity {ah / (ah + am + af) * 100:.0f}%"
            st = h.get("serving_stream_ttft")
            if st and st["count"]:
                p50 = histogram_percentile(st["buckets"], 0.5)
                if p50 is not None:
                    seg += f"  stream_ttft_p50<={p50 * 1e3:.2f}ms"
            lines.append(seg)
        # continuous-batching engine plane (serving/engine.py)
        if "serving_tokens_total" in c:
            seg = (f"engine: tokens {int(c['serving_tokens_total'])}  "
                   f"slots {int(g.get('serving_slots_active', 0))}  "
                   f"queue {int(g.get('serving_engine_queue', 0))}")
            tr = rate("serving_tokens_total")
            if tr is not None:
                seg += f"  tok/s {tr:.1f}"
            # the engine's page pool: physical page occupancy +
            # prefix-cache hit rate
            pt = g.get("serving_kv_pages_budget")
            if pt:
                free = g.get("serving_kv_pages_free", 0)
                seg += (f"  pages {int(pt - free)}/{int(pt)} "
                        f"({(pt - free) / pt * 100:.0f}%)")
            # share of the page table the decode steps had to walk: pages
            # live slots attended over steps x (slots x max_pages)
            table = (c.get("serving_engine_steps_total", 0)
                     * g.get("serving_engine_table_pages", 0))
            if table:
                walked = c.get("serving_engine_page_steps_total", 0)
                seg += f"  walk {walked / table * 100:.1f}%"
            # share of the keys its queries saw that the selection let into
            # the softmax: under 100% only where an indexer selects
            # (llm/latent.py); the model's arithmetic, not the kernel's walk
            seen = c.get("serving_engine_context_keys_total", 0)
            picked = c.get("serving_engine_selected_keys_total", 0)
            if seen and picked < seen:
                seg += f"  selected {picked / seen * 100:.0f}%"
            hits = int(c.get("serving_prefix_hits_total", 0))
            miss = int(c.get("serving_prefix_misses_total", 0))
            if hits + miss:
                seg += f"  prefix {hits / (hits + miss) * 100:.0f}%"
            # speculative decoding (serving/engine.py spec_decode):
            # accepted draft tokens / proposed — the knob that says
            # whether speculation is paying for its verify windows
            prop = int(c.get("serving_spec_proposed_total", 0))
            if prop:
                acc = int(c.get("serving_spec_accepted_total", 0))
                seg += f"  spec {acc / prop * 100:.0f}%"
            for label, key in (("ttft", "serving_ttft"),
                               ("tbt", "serving_tbt")):
                hh = h.get(key)
                if hh and hh["count"]:
                    p50 = histogram_percentile(hh["buckets"], 0.5)
                    if p50 is not None:
                        seg += f"  {label}_p50<={p50 * 1e3:.2f}ms"
            lines.append(seg)

    # ------------------------------------------------- live loop (ISSUE 15)
    # train → publish → hot-swap → serve as ONE line: training round vs
    # fleet version (the lag IS the loop's health), publish-to-serving
    # latency, and the loadgen's SLO ledger (shed ≠ error)
    if c.get("soak_publishes_total") or c.get("loadgen_requests_total"):
        seg = (f"loop: round {int(g.get('soak_loop_round', 0))}"
               f"  fleet_v {int(g.get('serving_fleet_version', 0))}"
               f"  lag {int(g.get('soak_fleet_lag_rounds', 0))}"
               f"  pub {int(c.get('soak_publishes_total', 0))}")
        rs = h.get("soak_round_to_serve_s")
        if rs and rs["count"]:
            p50 = histogram_percentile(rs["buckets"], 0.5)
            if p50 is not None:
                seg += f"  pub2serve_p50<={p50 * 1e3:.0f}ms"
        revived = int(c.get("soak_replica_revives_total", 0))
        if revived:
            seg += f"  revived {revived}"
        seg += (f"  load ok {int(c.get('loadgen_ok_total', 0))}"
                f" shed {int(c.get('loadgen_shed_total', 0))}"
                f" err {int(c.get('loadgen_errors_total', 0))}")
        tt = h.get("loadgen_ttft_s")
        if tt and tt["count"]:
            p99 = histogram_percentile(tt["buckets"], 0.99)
            if p99 is not None:
                seg += f"  ttft_p99<={p99 * 1e3:.0f}ms"
        if "soak_slo_ok" in g:
            seg += "  slo " + ("OK" if g["soak_slo_ok"] else "VIOLATED")
        lines.append(seg)

    # -------------------------------------------- attribution (ISSUE 17)
    # where the wall time went (fed.budget.* gauges from
    # utils/attribution.py) + the live SLO burn/alert state (utils/slo.py)
    if "fed_budget_wall_s" in g:
        by_bk = {k[len("fed_budget_transport_"):-len("_s")]: v
                 for k, v in g.items()
                 if k.startswith("fed_budget_transport_")
                 and k.endswith("_s") and k != "fed_budget_transport_s"}
        seg = (f"budget: wall {g['fed_budget_wall_s']:.1f}s"
               f"  transport {g.get('fed_budget_transport_share', 0):.0%}")
        if by_bk:
            seg += " (" + ", ".join(
                f"{b} {v:.1f}s" for b, v in sorted(by_bk.items())) + ")"
        seg += (f"  compute {g.get('fed_budget_compute_s', 0):.1f}s"
                f"  ingest {g.get('fed_budget_ingest_s', 0):.1f}s"
                f"  agg {g.get('fed_budget_agg_s', 0):.1f}s"
                f"  idle {g.get('fed_budget_idle_s', 0):.1f}s")
        lines.append(seg)
    if "slo_alerts_firing" in g or c.get("slo_alerts_total"):
        burns = {k[len("slo_burn_"):]: v for k, v in g.items()
                 if k.startswith("slo_burn_") and not k.endswith("_slow")}
        seg = (f"alerts: firing {int(g.get('slo_alerts_firing', 0))}"
               f"  fired_total {int(c.get('slo_alerts_total', 0))}")
        if burns:
            worst = max(burns.items(), key=lambda kv: kv[1])
            seg += "  burn " + " ".join(
                f"{k}:{v:.1f}x" for k, v in sorted(burns.items()))
            seg += f"  worst {worst[0]}"
        lines.append(seg)

    # ------------------------------------------------------------- retraces
    retr = {k: int(v) for k, v in c.items() if k.startswith("xla_retraces_")}
    if retr:
        lines.append("xla retraces: " + " ".join(
            f"{k[len('xla_retraces_'):-len('_total')]}:{v}"
            for k, v in sorted(retr.items())))
    return "\n".join(lines)


def cmd_top(args) -> int:
    """Live one-screen run health (reference: the MLOps run dashboard;
    local-first: scrape the run's /metrics endpoint — or read a finished
    run's end-of-run snapshot from its events file)."""
    import time as _time

    from .utils.prometheus import parse_prometheus, render_prometheus

    url = args.url
    if url is None and args.port is not None:
        url = f"http://127.0.0.1:{args.port}/metrics"
    if getattr(args, "fleet", False) and url is None:
        print("top --fleet needs --url/--port pointing at a "
              "FleetCollector's aggregated /metrics "
              "(common_args.extra.obs_fleet.port)", file=sys.stderr)
        return 2
    # the run-dir fallback reads a FINISHED run's static end-of-run
    # snapshot — looping over it would render the same frame forever
    once = args.once or url is None

    def fetch() -> tuple[dict, str]:
        if url:
            import urllib.request

            with urllib.request.urlopen(url, timeout=5) as r:
                return parse_prometheus(r.read().decode()), url
        # run-dir fallback: the end-of-run metrics snapshot that
        # mlops.finish appended to the newest events file; rendering it
        # through the same exposition + parser normalizes the names
        path = _newest_events_file(args.log_dir, args.run)
        report = None
        with open(path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "report" in row:
                    report = row["report"]
        if report is None or "metrics" not in report:
            raise ValueError(
                f"{path} has no end-of-run metrics snapshot (run without "
                "mlops.finish?) — use --url against a live run")
        return parse_prometheus(
            render_prometheus(report["metrics"])), path

    prev, prev_t = None, None
    frame = 0
    misses = 0
    try:
        while True:
            try:
                snap, source = fetch()
                misses = 0
            except Exception as e:  # noqa: BLE001 — operator-facing CLI
                # a failure before the first frame (or in one-shot mode) is
                # a hard error; inside a live watch a transient scrape miss
                # (brief GC pause, connection reset) just skips the frame —
                # until several in a row say the endpoint is really gone
                misses += 1
                print(f"top: {type(e).__name__}: {e}", file=sys.stderr)
                if frame == 0 or once or misses >= 5:
                    return 1
                _time.sleep(args.interval)
                continue
            now = _time.monotonic()
            if getattr(args, "fleet", False):
                # fleet mode (ISSUE 18): the scraped exposition is the
                # collector's AGGREGATE — split it back per process and
                # render the per-process-columns table
                from .utils.obsfleet import fleet_sums
                from .utils.prometheus import split_by_label

                split = split_by_label(snap, "process")
                per = {k: v for k, v in split.items() if k}
                # the collector's own (unlabeled) families carry the
                # fleet-level staleness gauge
                n_stale = ((split.get("") or {}).get("gauges")
                           or {}).get("obs_fleet_stale")
                fs = {"processes": {
                    n: {"ok": True, "stale": False, "age_s": None,
                        "error": None, "snapshot": s}
                    for n, s in per.items()},
                    "sums": fleet_sums(per)}
                head = (f"fedml_tpu top --fleet — {source}  "
                        f"({_time.strftime('%Y-%m-%d %H:%M:%S')})")
                if n_stale:
                    head += f"  STALE PROCESSES: {int(n_stale)}"
                text = head + "\n" + _render_fleet(fs)
            else:
                text = _top_frame(
                    snap, source, prev,
                    (now - prev_t) if prev_t is not None else None)
            if not once and frame:
                print("\x1b[2J\x1b[H", end="")  # clear screen between frames
            print(text, flush=True)
            frame += 1
            if once or (args.frames and frame >= args.frames):
                return 0
            prev, prev_t = snap, now
            _time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0        # ^C is the documented way to stop a live watch


def cmd_lint(args) -> int:
    """graftlint — the repo-native static-analysis plane (ISSUE 13).
    Machine-checks the invariants the review passes used to catch by
    hand: donated-buffer discipline, retrace hazards, serve-knob drift,
    metric-name consistency, lock discipline in serving/comm, in-trace
    purity. Exit 0 = clean, 1 = findings, 2 = usage error. `--format
    json` emits the stable schema external CI consumes (README "Static
    analysis")."""
    from .analysis import all_rules, render_json, render_text, run_lint

    if args.list_rules:
        for r in all_rules():
            print(f"{r.name}: {r.summary}")
        return 0
    rules = None
    if args.rules:
        rules = [t.strip() for t in args.rules.split(",") if t.strip()]
    try:
        findings, stats = run_lint(paths=args.paths or None, rules=rules)
    except (ValueError, OSError) as e:
        print(f"lint: {e}", file=sys.stderr)
        return 2
    print(render_json(findings, stats) if args.format == "json"
          else render_text(findings, stats))
    return 1 if findings else 0


def _forced_2dev_subprocess(child_src: str, label: str,
                            timeout: int = 240) -> dict:
    """Run `child_src` in a fresh interpreter whose host CPU platform is
    FORCED to 2 devices (this process's jax is already initialized, so the
    forced-device flag must be set before a new interpreter boots). The
    child must print one JSON object as its last stdout line. Shared by
    every diagnosis probe that needs a real multi-device mesh on a
    single-device host."""
    import os as _os
    import subprocess as _sp
    import sys as _sys
    from pathlib import Path as _Path

    env = {**_os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "PYTHONPATH": _os.pathsep.join(
               [str(_Path(__file__).resolve().parent.parent)]
               + ([_os.environ["PYTHONPATH"]]
                  if _os.environ.get("PYTHONPATH") else []))}
    r = _sp.run([_sys.executable, "-c", child_src], capture_output=True,
                text=True, timeout=timeout, env=env)
    if r.returncode != 0:
        raise RuntimeError(
            f"forced-2-device {label} child failed: {r.stderr[-300:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _cohort_sharded_check() -> dict:
    """Shared body of the `cohort_sharded_smoke` diagnosis probe, importable
    so the forced-2-device subprocess runs the IDENTICAL check this process
    runs when it already has a multi-device platform: a 2-chunk streamed
    cohort round over a real `clients` mesh must be bitwise the single-shot
    round (history AND params), with ingest overlap observed and a bounded
    chunk-program count."""
    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu.simulation.simulator import Simulator
    from fedml_tpu.utils import metrics as mx

    d = len(jax.devices())
    m = 2 * d

    def cfg(extra=None):
        return fedml_tpu.init(config={
            "common_args": {"training_type": "simulation", "random_seed": 0},
            "data_args": {"dataset": "synthetic",
                          "extra": {"synthetic_samples_per_client": 8}},
            "model_args": {"model": "lr"},
            "train_args": {"federated_optimizer": "FedAvg",
                           "client_num_in_total": m,
                           "client_num_per_round": m,
                           "comm_round": 2, "epochs": 1, "batch_size": 8,
                           "learning_rate": 0.1, "extra": extra or {}},
            "validation_args": {"frequency_of_the_test": 0},
            "comm_args": {"backend": "xla"},
        })

    before = mx.snapshot()["counters"]
    chk = Simulator(cfg({"cohort_chunk": d, "ingest_prefetch": 1}))
    if chk.mesh is None or chk.mesh.devices.size != d:
        raise RuntimeError("chunked sim did not build the client mesh")
    chk.run()
    after = mx.snapshot()["counters"]
    chunks = (after.get("fed.ingest.chunks", 0)
              - before.get("fed.ingest.chunks", 0))
    prefetched = (after.get("fed.ingest.prefetched", 0)
                  - before.get("fed.ingest.prefetched", 0))
    ref = Simulator(cfg())
    ref.run()
    if ref.history != chk.history:
        raise ValueError("chunked round history diverged from single-shot")
    for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(ref.server_state.params)),
            jax.tree_util.tree_leaves(jax.device_get(chk.server_state.params))):
        if not np.array_equal(a, b):
            raise ValueError("chunked params not bitwise-identical to the "
                             "single-shot round")
    if chunks < 4:   # 2 rounds x 2 chunks each
        raise ValueError(f"expected >=4 streamed chunks, saw {chunks}")
    if prefetched < 1:
        raise ValueError("ingest never overlapped compute: no chunk was "
                         "resident before the consumer asked")
    n_chunk = chk.chunk_fn._fn._cache_size()
    if n_chunk != 1:
        raise ValueError(f"chunk program retraced: {n_chunk} compiles")
    return {"devices": d, "chunks": int(chunks),
            "prefetched": int(prefetched), "params_bitwise": True}


# fleet_obs_smoke children (jax-free on purpose — interpreter start must
# stay inside the probe's 20s budget). Peers exchange reliable gRPC
# traffic both ways (pings out, pongs back — both clock-offset directions
# get constraints), export their Chrome traces, then serve /metrics and
# block on stdin until the parent is done scraping. The victim arms the
# flight recorder on a fast spill cadence and heartbeats until SIGKILLed.
_FLEET_PEER_SRC = """\
import json, sys, threading, time
from fedml_tpu.comm.manager import FedCommManager
from fedml_tpu.comm.message import Message
from fedml_tpu.comm.grpc_transport import GrpcTransport
from fedml_tpu.comm.reliable import ReliableTransport, RetryPolicy
from fedml_tpu.utils.events import recorder
from fedml_tpu.utils.prometheus import MetricsExporter

rank = {rank}
n = {n}
ipmap = {{0: "127.0.0.1:{port_a}", 1: "127.0.0.1:{port_b}"}}
t = ReliableTransport(
    GrpcTransport(rank, ipmap, port={my_port}),
    RetryPolicy(ack_timeout_s=0.2, max_attempts=20, deadline_s=20.0))
m = FedCommManager(t, rank)
got = set()
done = threading.Event()

def on_msg(msg):
    got.add(msg.get("i"))
    if rank == 1:
        m.send_message(Message("fleet_pong", 1, 0).add("i", msg.get("i")))
    if len(got) >= n:
        done.set()

m.register_message_receive_handler(
    "fleet_ping" if rank == 1 else "fleet_pong", on_msg)
m.run(background=True)
if rank == 0:
    time.sleep(0.4)
    for i in range(n):
        m.send_message(Message("fleet_ping", 0, 1).add("i", i))
ok = done.wait(timeout=20)
recorder.export_chrome_trace(r"{trace}")
exp = MetricsExporter(port=0).start()
print(json.dumps({{"ok": bool(ok), "url": exp.url, "got": len(got)}}),
      flush=True)
sys.stdin.read()
m.stop()
"""

_FLEET_VICTIM_SRC = """\
import json, sys, time
from fedml_tpu.utils import metrics as mx
from fedml_tpu.utils import postmortem
from fedml_tpu.utils.events import recorder
from fedml_tpu.utils.prometheus import MetricsExporter

postmortem.flight.spill_every_s = 0.05
postmortem.arm(r"{run_dir}", process="victim")
mx.inc("victim.steps")
with recorder.span("victim.work", step=0):
    pass
exp = MetricsExporter(port=0).start()
print(json.dumps({{"url": exp.url}}), flush=True)
while True:
    with recorder.span("victim.heartbeat"):
        time.sleep(0.05)
"""


def cmd_diagnosis(args) -> int:
    """Connectivity / capability checks (reference:
    slave/client_diagnosis.py — MQTT + S3 probes before joining a run).
    Probes every transport the comm layer offers plus the device runtime;
    exit 0 iff everything required works."""
    import uuid

    checks: dict = {}

    def check(name, fn):
        try:
            checks[name] = {"ok": True, **(fn() or {})}
        except Exception as e:  # noqa: BLE001 — each probe reports
            checks[name] = {"ok": False,
                            "error": f"{type(e).__name__}: {e}"[:200]}

    def jax_devices():
        import jax

        return {"backend": jax.default_backend(),
                "devices": len(jax.devices())}

    def loopback():
        from .comm import FedCommManager, Message
        from .comm.loopback import LoopbackTransport, release_router

        run = f"diag-{uuid.uuid4().hex[:6]}"
        import threading

        got = threading.Event()
        a = FedCommManager(LoopbackTransport(0, run), 0)
        b = FedCommManager(LoopbackTransport(1, run), 1)
        b.register_message_receive_handler("ping", lambda m: got.set())
        a.run(background=True)
        b.run(background=True)
        a.send_message(Message("ping", 0, 1))
        ok = got.wait(timeout=5)
        a.stop(); b.stop(); release_router(run)
        if not ok:
            raise TimeoutError("loopback roundtrip timed out")

    def grpc():
        from .comm.grpc_transport import GrpcTransport

        # bind-probe on an ephemeral port proves the stack is usable
        t = GrpcTransport(0, {}, port=0)
        t.shutdown(grace=0)

    def native():
        from .native import crc32c

        if crc32c(b"x") is None:
            raise RuntimeError("native lib unavailable (pure-python "
                               "fallbacks active — functional, slower)")

    def wire():
        import numpy as np

        from .comm.serialization import decode, encode

        x = {"a": np.arange(8, dtype=np.float32)}
        got = decode(encode(x))
        if not np.array_equal(got["a"], x["a"]):
            raise ValueError("wire codec roundtrip mismatch")

    def metrics_endpoint():
        # the run-health export plane end-to-end: bind an ephemeral
        # /metrics server, scrape it, and PARSE the exposition (the same
        # parser `fedml_tpu top` uses) — proves the scrape surface a
        # monitoring stack would attach to actually works on this host
        import urllib.request

        from .utils import metrics as mx
        from .utils.prometheus import MetricsExporter, parse_prometheus

        mx.inc("diagnosis.metrics_probe")
        exp = MetricsExporter(port=0).start()
        try:
            with urllib.request.urlopen(exp.url, timeout=5) as r:
                text = r.read().decode()
            parsed = parse_prometheus(text)
            if "diagnosis_metrics_probe_total" not in parsed["counters"]:
                raise ValueError("probe counter missing from exposition")
            return {"port": exp.port,
                    "series": len(parsed["counters"])
                    + len(parsed["gauges"]) + len(parsed["histograms"])}
        finally:
            exp.stop()

    def chaos_smoke():
        # the robustness plane end-to-end (ISSUE 4): a 2-rank loopback
        # exchange under injected drop/duplicate/delay/corrupt faults, with
        # the reliable layer stacked on — every message must land exactly
        # once. Proves the chaos + retry/dedup machinery works on this host.
        import threading as _th
        import time as _t

        from .comm import FedCommManager, Message
        from .comm.chaos import ChaosTransport, FaultSpec
        from .comm.loopback import LoopbackTransport, release_router
        from .comm.reliable import ReliableTransport, RetryPolicy
        from .utils import metrics as mx

        run = f"chaos-{uuid.uuid4().hex[:6]}"
        spec = FaultSpec(seed=7, drop=0.2, duplicate=0.15, delay=0.3,
                         delay_max_s=0.01, corrupt=0.1)
        pol = RetryPolicy(ack_timeout_s=0.05, max_attempts=10,
                          deadline_s=15.0)
        mk = lambda r: ReliableTransport(  # noqa: E731
            ChaosTransport(LoopbackTransport(r, run), spec), pol)
        a, b = FedCommManager(mk(0), 0), FedCommManager(mk(1), 1)
        got: list = []
        done = _th.Event()
        n = 20

        def on_probe(m):
            got.append(m.get("i"))
            if len(set(got)) >= n:
                done.set()

        b.register_message_receive_handler("chaos_probe", on_probe)
        a.run(background=True)
        b.run(background=True)
        try:
            for i in range(n):
                a.send_message(Message("chaos_probe", 0, 1).add("i", i))
            ok = done.wait(timeout=15)
            _t.sleep(0.1)      # let straggling duplicates land (dedup check)
            if not ok or sorted(set(got)) != list(range(n)):
                raise TimeoutError(
                    f"delivered {len(set(got))}/{n} under injected faults")
            if len(got) != len(set(got)):
                raise ValueError("dedup window failed: a message was "
                                 "applied twice")
            snap = mx.snapshot()["counters"]
            return {"delivered": n,
                    "faults_injected": sum(
                        v for k, v in snap.items()
                        if k.startswith("fed.chaos.")),
                    "retransmits": snap.get("comm.rel.retransmits", 0)}
        finally:
            a.stop()
            b.stop()
            release_router(run)

    def serving_engine_smoke():
        # the continuous-batching plane end-to-end (ISSUE 5): a tiny LM on
        # the slot engine, 8 concurrent requests — every request must get
        # exactly one response, more than one slot must have been active
        # at once, and the compiled-program set must stay bounded (one
        # step program + one admit program per chunk bucket).
        import threading as _th
        import time as _t

        import jax as _jax
        import jax.numpy as _jnp
        import numpy as _np

        from .llm.transformer import TransformerLM
        from .serving.engine import DecodeEngine
        from .utils import metrics as mx

        model = TransformerLM(vocab_size=64, d_model=32, n_layers=1,
                              n_heads=2, d_ff=64, scan_layers=True)
        params = model.init(_jax.random.key(0),
                            _jnp.zeros((1, 8), _jnp.int32))["params"]
        rs = _np.random.RandomState(0)
        prompts = [rs.randint(1, 64, n).tolist()
                   for n in (4, 6, 5, 7, 4, 6, 5, 7)]
        eng = DecodeEngine(model, params, n_slots=4, max_len=32).start()
        max_active = [0]
        stop = _th.Event()

        def poll():
            g = mx.registry.gauge("serving.slots_active")
            while not stop.is_set():
                max_active[0] = max(max_active[0], int(g.value()))
                _t.sleep(0.002)

        _th.Thread(target=poll, daemon=True).start()
        try:
            tickets = [eng.submit(p, 6) for p in prompts]
            outs = [t.result(timeout=60) for t in tickets]
        finally:
            stop.set()
            counts = eng.program_counts()
            eng.stop()
        if len(outs) != 8 or any(len(o) != 6 for o in outs):
            raise ValueError(f"responses malformed: {[len(o) for o in outs]}")
        if max_active[0] <= 1:
            raise ValueError("slots never decoded concurrently "
                             f"(max slots_active {max_active[0]})")
        if counts["step"] != 1:
            raise ValueError(f"step program retraced: {counts}")
        if counts["admit"] > 2:
            raise ValueError(f"admit programs unbounded: {counts}")
        return {"requests": 8, "max_slots_active": max_active[0],
                "programs": counts}

    def serving_paged_smoke():
        # the engine's page pool end-to-end (ISSUE 7): a tiny LM under a
        # page budget below what every slot at max_len would take (the
        # default pool), 6 concurrent requests sharing a common prompt
        # prefix — allocation must serve all of them, the prefix cache
        # must hit (the shared head is resident after the first
        # admission), retirement must reclaim pages (free + resident
        # prefix pages == the full budget afterwards), and the compiled-
        # program set must stay bounded (one step + pow2 chunk buckets).
        import jax as _jax
        import jax.numpy as _jnp
        import numpy as _np

        from .llm.transformer import TransformerLM
        from .serving.engine import DecodeEngine
        from .utils import metrics as mx

        model = TransformerLM(vocab_size=64, d_model=32, n_layers=1,
                              n_heads=2, d_ff=64, scan_layers=True)
        params = model.init(_jax.random.key(0),
                            _jnp.zeros((1, 8), _jnp.int32))["params"]
        rs = _np.random.RandomState(0)
        head = rs.randint(1, 64, 8).tolist()    # shared 2-page prefix
        # 12-token prompts (4-token suffixes): every chunk is exactly one
        # bucket, so the probe compiles ONE chunk program + one step —
        # this probe runs twice inside tier-1, keep it lean
        prompts = [head + rs.randint(1, 64, 4).tolist() for _ in range(6)]
        # 19 usable pages vs the default pool's
        # slots * max_len / page_size = 3 * 32 / 4 = 24
        eng = DecodeEngine(model, params, n_slots=3, max_len=32,
                           page_size=4, n_pages=20, prefill_chunk=4).start()
        try:
            tickets = [eng.submit(p, 4) for p in prompts]
            outs = [t.result(timeout=60) for t in tickets]
            counts = eng.program_counts()
            snap = mx.snapshot()
            free = snap["gauges"]["serving.kv_pages_free"]
            resident = len(eng._prefix)
        finally:
            eng.stop()
        if len(outs) != 6 or any(len(o) != 4 for o in outs):
            raise ValueError(f"responses malformed: {[len(o) for o in outs]}")
        hits = snap["counters"].get("serving.prefix_hits", 0)
        if hits < 1:
            raise ValueError("shared prompt prefix never hit the "
                             f"prefix cache (hits {hits})")
        if free + resident != 19:
            raise ValueError(
                f"retirement did not reclaim pages: free {free} + "
                f"resident prefix {resident} != budget 19")
        if counts["step"] != 1:
            raise ValueError(f"paged step retraced: {counts}")
        if counts["admit"] > 1:
            raise ValueError(f"chunk programs unbounded: {counts}")
        return {"requests": 6, "prefix_hits": int(hits),
                "pages_free": int(free), "prefix_resident": resident,
                "programs": counts}

    def serving_spec_smoke():
        # the decode-speed plane end-to-end (ISSUE 11): 4 concurrent
        # requests with repetitive (acceptance-friendly) prompts through
        # the PAGED engine with n-gram speculation on — drafts must
        # actually be accepted (accepted > 0), the emitted tokens must be
        # token-identical to the same engine with speculation off (the
        # greedy-exact contract), and the compiled-program set must stay
        # bounded (ONE verify window program, zero plain-step programs).
        import jax as _jax
        import jax.numpy as _jnp

        from .llm.transformer import TransformerLM
        from .serving.engine import DecodeEngine
        from .utils import metrics as mx

        model = TransformerLM(vocab_size=64, d_model=32, n_layers=1,
                              n_heads=2, d_ff=64, scan_layers=True)
        params = model.init(_jax.random.key(0),
                            _jnp.zeros((1, 8), _jnp.int32))["params"]
        # repetitive prompts: the trailing bigram always has an earlier
        # occurrence, so the self-draft proposes the loop's continuation.
        # All length 8 = exactly two 4-token chunks — ONE chunk program
        # per engine; this probe runs twice inside tier-1, keep it lean
        prompts = [[3, 9] * 4, [2] * 8, [11, 5, 7, 11, 5, 7, 11, 5],
                   [7] * 8]

        def run(spec):
            eng = DecodeEngine(
                model, params, n_slots=4, max_len=32, page_size=4,
                prefill_chunk=4, spec_decode="ngram" if spec else "off",
                spec_k=3).start()
            try:
                tickets = [eng.submit(p, 6) for p in prompts]
                outs = [t.result(timeout=60) for t in tickets]
                return outs, eng.program_counts()
            finally:
                eng.stop()

        base, _counts = run(spec=False)
        # DELTA across the spec run, not process-lifetime absolutes — an
        # earlier spec engine in this process (tier-1 runs this probe
        # in-process) must not satisfy the accepted>0 bar for it
        c0 = mx.snapshot()["counters"]
        got, counts = run(spec=True)
        c1 = mx.snapshot()["counters"]
        accepted = int(c1.get("serving.spec.accepted", 0)
                       - c0.get("serving.spec.accepted", 0))
        proposed = int(c1.get("serving.spec.proposed", 0)
                       - c0.get("serving.spec.proposed", 0))
        if got != base:
            raise ValueError(
                "speculation-on output differs from speculation-off — "
                "the greedy-exact acceptance contract is broken")
        if accepted < 1:
            raise ValueError(
                f"no draft token was ever accepted on repetitive "
                f"prompts (proposed {proposed})")
        if counts.get("verify") != 1:
            raise ValueError(f"verify program retraced: {counts}")
        if counts["step"] != 0:
            raise ValueError(
                f"spec engine dispatched plain steps: {counts}")
        return {"requests": len(prompts), "accepted": accepted,
                "proposed": proposed,
                "accept_rate": round(accepted / max(proposed, 1), 3),
                "programs": counts}

    def serving_density_smoke():
        # the serving-density plane end-to-end (ISSUE 16): the same
        # prompts through (1) the baseline paged engine, (2) int8 KV
        # pages, (3) int8 + batched admission. int8 is judged
        # TEACHER-FORCED on 512 tokens — prompt + the baseline's first k
        # tokens resubmitted for ONE token, compared with the baseline's
        # (k+1)-th — so a near-tie flip costs one sample, not its whole
        # greedy tail, and the 0.99 bar is a statement about
        # quantisation rather than about which three tokens a free run
        # happened to lose. Random toy weights have no logit margins to
        # speak of (undamped, rounding-level noise alone flips ~1.2% of
        # argmaxes: 506/512 measured), so the attention branch runs at
        # quarter weight: quantisation noise then decides ~0.4% of
        # tokens (510/512) while a real defect still wrecks the stream
        # (scales off by 1.3x: 17/512). Besides: batched admission must
        # not change a token, the serving.kv_bytes_per_slot gauge must
        # show >= 2x density (int8 pool + f32 per-page-per-head scales
        # vs the baseline pool at the same slot/page geometry), and
        # batched admission must have compiled a bounded set of batch
        # programs while recording its serving.engine.admit_batch
        # histogram.
        import jax as _jax
        import jax.numpy as _jnp
        import numpy as _np

        from .llm.transformer import TransformerLM
        from .serving.engine import DecodeEngine
        from .utils import metrics as mx

        model = TransformerLM(vocab_size=64, d_model=32, n_layers=1,
                              n_heads=2, d_ff=64, scan_layers=True)
        params = model.init(_jax.random.key(0),
                            _jnp.zeros((1, 8), _jnp.int32))["params"]
        wo = params["blocks"]["wo"]
        params = {**params, "blocks": {
            **params["blocks"], "wo": {**wo, "kernel": wo["kernel"] * 0.25}}}
        rs = _np.random.RandomState(0)
        # all length 8 = exactly two 4-token chunks: one chunk program
        # on the unbatched engines, one batch bucket on the batched one
        prompts = [rs.randint(1, 64, 8).tolist() for _ in range(32)]
        burst, new = prompts[:4], 16

        def run(reqs, forced=None, **kw):
            eng = DecodeEngine(model, params, n_slots=4, max_len=32,
                               page_size=4, prefill_chunk=4, **kw).start()
            try:
                tickets = [eng.submit(p, new) for p in reqs]
                r = {"outs": [t.result(timeout=60) for t in tickets],
                     "counts": eng.program_counts()}
                if forced is not None:
                    picks = [(eng.submit(pr + ob[:k], 1), ob[k])
                             for pr, ob in zip(prompts, forced)
                             for k in range(len(ob))]
                    r["total"] = len(picks)
                    r["matched"] = sum(t.result(timeout=60)[0] == want
                                       for t, want in picks)
                r["bps"] = int(
                    mx.snapshot()["gauges"]["serving.kv_bytes_per_slot"])
                return r
            finally:
                eng.stop()

        base = run(prompts)
        h0 = mx.snapshot()["histograms"].get(
            "serving.engine.admit_batch", {}).get("count", 0)
        quant = run(burst, forced=base["outs"], kv_quant="int8")
        batched = run(burst, kv_quant="int8", admit_batch=4)
        h1 = mx.snapshot()["histograms"].get(
            "serving.engine.admit_batch", {}).get("count", 0)
        matched, total = quant["matched"], quant["total"]
        bps_base, bps_q = base["bps"], quant["bps"]
        counts = batched["counts"]
        if matched / total < 0.99:
            raise ValueError(
                f"int8 KV pages diverged from the baseline: "
                f"{matched}/{total} teacher-forced greedy tokens matched "
                "(bar 0.99)")
        if batched["outs"] != quant["outs"]:
            raise ValueError(
                "batched admission changed int8 outputs — admission "
                "grouping must be invisible to decoded tokens")
        if bps_q * 2 > bps_base:
            raise ValueError(
                f"int8 pool density below 2x: {bps_q} bytes/slot vs "
                f"baseline {bps_base}")
        nb = counts.get("admit_batch")
        if not nb or nb > 3:
            raise ValueError(f"batch programs unbounded or absent: {counts}")
        if h1 <= h0:
            raise ValueError("serving.engine.admit_batch never recorded")
        return {"requests": len(prompts) + 2 * len(burst) + total,
                "match_rate": round(matched / total, 4),
                "teacher_forced_tokens": total,
                "kv_bytes_per_slot": {"base": bps_base, "int8": bps_q},
                "density_x": round(bps_base / bps_q, 2),
                "admit_batches": int(h1 - h0), "programs": counts}

    def fleet_rolling_update_smoke():
        # the serving-fleet robustness plane end-to-end (ISSUE 9): a
        # 2-replica engine-backed LM deployment under sustained
        # concurrent load takes a v1 -> v2 adapter hot swap through the
        # rolling updater — zero non-2xx responses (no shedding armed,
        # so NONE are deliberate), both replicas report model_version 2
        # on /info afterwards, and a streamed request records a
        # first-token time. The zero-dropped bar is the whole point:
        # model churn must not cost requests.
        import json as _json
        import urllib.request as _ur

        from .serving.fleet_harness import FleetHarness
        from .utils import metrics as mx

        fleet = FleetHarness()    # probe-lean dims are the harness defaults
        try:
            gw = fleet.gateway()
            url = f"http://127.0.0.1:{gw.port}/predict"
            results, stop_load = fleet.sustained_load(
                url, 3, {"tokens": fleet.prompt, "max_new_tokens": 4})
            updated, _swap_s = fleet.publish_and_roll(version=2,
                                                      timeout=30)
            # one streamed request through the gateway records TTFT
            req = _ur.Request(url, data=_json.dumps(
                {"tokens": fleet.prompt, "max_new_tokens": 4,
                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            with _ur.urlopen(req, timeout=60) as r:
                body = r.read().decode()
            stop_load(timeout=10)
            versions = fleet.dep.versions()
        finally:
            fleet.close()
        codes = [cd for cd, _lat in results]
        bad = [cd for cd in codes if cd != 200]
        if bad:
            raise ValueError(
                f"rolling update dropped requests: {len(bad)}/{len(codes)} "
                f"non-2xx (codes {sorted(set(bad))})")
        if len(updated) != 2 or any(v != 2 for v in versions.values()):
            raise ValueError(f"fleet did not converge on v2: {versions}")
        if '"done": true' not in body:
            raise ValueError("streamed response never completed")
        snap = mx.snapshot()
        if not snap["histograms"].get("serving.stream_ttft", {}).get(
                "count"):
            raise ValueError("serving.stream_ttft never recorded")
        return {"requests_under_swap": len(codes), "non_2xx": 0,
                "versions": versions,
                "swaps": int(snap["counters"].get(
                    "serving.engine.swaps", 0))}

    def partition_rules_smoke():
        # the partitioning plane end-to-end (ISSUE 6): build the registry,
        # resolve the flagship TransformerLM in its serving shape (scan
        # layout + int8 base) and its LoRA adapters under the DEFAULT
        # error policy — full coverage and no ambiguity or this raises —
        # then build an {"mp": 2} mesh and actually shard the resolved
        # tree onto it: in-process when this host already has >= 2
        # devices, else in a subprocess whose host platform is FORCED to
        # 2 devices (this process's jax is already initialized, so the
        # forced-device flag must be set before a fresh interpreter boots)
        import jax as _jax
        import jax.numpy as _jnp

        from .llm.lora import lora_init
        from .llm.quant import quantize_tree_int8
        from .llm.transformer import TransformerLM
        from .parallel import partition as part

        model = TransformerLM(vocab_size=64, d_model=32, n_layers=2,
                              n_heads=2, d_ff=64, scan_layers=True)
        params = model.init(_jax.random.key(0),
                            _jnp.zeros((1, 8), _jnp.int32))["params"]
        specs = part.resolve("transformer_lm", quantize_tree_int8(params))
        part.resolve("lora", lora_init(_jax.random.key(1), params, rank=2))
        if len(_jax.devices()) >= 2:
            # this process already has a multi-device platform (real TPU
            # slice, or a test run under the forced-device conftest):
            # shard in-process — no ~15s subprocess jax cold-start
            from .parallel.mesh import make_mesh

            sh = part.shard_params(params, make_mesh({"mp": 2}),
                                   "transformer_lm")
            wq = sh["blocks"]["wq"]["kernel"]
            if len(wq.sharding.device_set) != 2:
                raise RuntimeError(f"wq not sharded: {wq.sharding}")
            return {"resolved_params":
                    len(_jax.tree_util.tree_leaves(specs)),
                    "devices": len(_jax.devices()),
                    "wq_spec": str(wq.sharding.spec),
                    "mode": "in-process"}
        child = (
            "import json, jax, jax.numpy as jnp\n"
            "from fedml_tpu.llm.transformer import TransformerLM\n"
            "from fedml_tpu.parallel import partition as part\n"
            "from fedml_tpu.parallel.mesh import make_mesh\n"
            "m = TransformerLM(vocab_size=64, d_model=32, n_layers=2,\n"
            "                  n_heads=2, d_ff=64, scan_layers=True)\n"
            "p = m.init(jax.random.key(0),\n"
            "           jnp.zeros((1, 8), jnp.int32))['params']\n"
            "sh = part.shard_params(p, make_mesh({'mp': 2}),\n"
            "                       'transformer_lm')\n"
            "wq = sh['blocks']['wq']['kernel']\n"
            "assert len(wq.sharding.device_set) == 2, wq.sharding\n"
            "print(json.dumps({'devices': len(jax.devices()),\n"
            "                  'wq_spec': str(wq.sharding.spec)}))\n")
        mesh_child = _forced_2dev_subprocess(child, "mesh")
        return {"resolved_params": len(_jax.tree_util.tree_leaves(specs)),
                **mesh_child, "mode": "forced-2-device subprocess"}

    def lint_clean():
        # the static-analysis plane end-to-end (ISSUE 13): graftlint over
        # the whole package tree must report ZERO findings — the same gate
        # tier-1 asserts and the Docker image build enforces. Pure-AST, so
        # it costs ~1s of the battery; --only lint_clean re-checks it
        # alone after a fix.
        import time as _time

        from .analysis import run_lint

        t0 = _time.perf_counter()
        findings, stats = run_lint()
        dt = _time.perf_counter() - t0
        if findings:
            raise ValueError(
                f"{len(findings)} graftlint finding(s); first: "
                f"{findings[0].format()}")
        if dt > 20:
            raise RuntimeError(
                f"tree scan took {dt:.1f}s (budget 20s) — the lint gate "
                "is too slow for CI")
        return {"files": stats["files"], "rules": len(stats["rules"]),
                "suppressed": stats["suppressed"],
                "scan_s": round(dt, 3)}

    def cross_silo_durability_smoke():
        # the crash-durability plane end-to-end (ISSUE 10): an in-process
        # loopback federation whose server is SIGKILL-severed mid-run (no
        # farewell, no checkpoint flush, stale frames left in flight) and
        # restarted with `resume` — the run must complete (the resumed
        # server initiates the re-handshake; the client watchdog is the
        # slow-restart backstop) and the final full-participation params
        # must be BITWISE-equal to an uninterrupted run's. Budget-lean:
        # two 3-round lr federations sharing one jit cache.
        import tempfile

        import jax as _jax
        import numpy as _np

        from .cross_silo.soak import (
            server_kill_restart_soak, uninterrupted_final_params,
        )

        ref, _hist = uninterrupted_final_params(n_clients=2, rounds=3)
        with tempfile.TemporaryDirectory() as d:
            out = server_kill_restart_soak(d, n_clients=2, rounds=3,
                                           kill_after=1)
        if out["error"]:
            raise RuntimeError(f"resumed run failed: {out['error']}")
        if [h["round"] for h in out["history"]] != [0, 1, 2]:
            raise ValueError(f"resumed history malformed: {out['history']}")
        eq = all(_jax.tree.leaves(_jax.tree.map(
            lambda a, b: bool(_np.array_equal(a, b)), ref, out["params"])))
        if not eq:
            raise ValueError("resumed final params differ bitwise from the "
                             "uninterrupted run")
        if out["resumes"] < 1:
            raise ValueError("server never recorded a resume")
        return {"rounds": len(out["history"]),
                "recovery_s": round(out["recovery_s"], 3),
                "resumes": out["resumes"],
                "stale_gen_rejected": out["stale_gen_rejected"],
                "generation": out["generation"]}

    def cohort_sharded_smoke():
        # the Parrot-scale simulation plane end-to-end (ISSUE 8): a
        # chunked+streamed cohort round over a REAL multi-device mesh ==
        # the single-shot round bitwise, with ingest overlap observed.
        # In-process when this host already has >= 2 devices; otherwise a
        # forced-2-device subprocess (same pattern as partition_rules_smoke
        # — this process's jax platform is already initialized).
        import jax as _jax

        if len(_jax.devices()) >= 2:
            return {**_cohort_sharded_check(), "mode": "in-process"}
        child = (
            "import json\n"
            "from fedml_tpu.__main__ import _cohort_sharded_check\n"
            "print(json.dumps(_cohort_sharded_check()))\n")
        return {**_forced_2dev_subprocess(child, "cohort"),
                "mode": "forced-2-device subprocess"}

    def codec_smoke():
        # the wire-codec plane end-to-end (ISSUE 14): a 2-rank loopback
        # round of model-payload frames through the SPARSE codec under
        # chaos corrupt/duplicate injection with reliable delivery stacked
        # on — every payload must land exactly once, decode to the sender-
        # side reconstruction bit-for-bit, and cost fewer wire bytes than
        # raw. Proves compression, validation, and exactly-once dispatch
        # compose on this host.
        import threading as _th
        import time as _t

        import numpy as _np

        from .comm import FedCommManager, Message
        from .comm.chaos import ChaosTransport, FaultSpec
        from .comm.codec import CodecPolicy
        from .comm.loopback import LoopbackTransport, release_router
        from .comm.reliable import ReliableTransport, RetryPolicy
        from .compression import decode_sparse, encode_sparse
        from .utils import metrics as mx

        run = f"codec-{uuid.uuid4().hex[:6]}"
        spec = FaultSpec(seed=11, duplicate=0.2, corrupt=0.15, drop=0.1)
        pol = RetryPolicy(ack_timeout_s=0.05, max_attempts=10,
                          deadline_s=15.0)
        cc = {"kind": "sparse_topk", "ratio": 0.25,
              "per_type": {"codec_probe": "sparse_topk"}}

        def mk(r):
            base = LoopbackTransport(r, run)
            base.set_codec(CodecPolicy.from_config(cc))
            return ReliableTransport(ChaosTransport(base, spec), pol)

        a, b = FedCommManager(mk(0), 0), FedCommManager(mk(1), 1)
        got: dict = {}
        done = _th.Event()
        n = 12
        rs = _np.random.RandomState(3)
        payloads = [rs.randn(257).astype(_np.float32) for _ in range(n)]

        def on_probe(m):
            got.setdefault(int(m.get("i")), []).append(
                _np.asarray(m.get("model_params")["w"]))
            if len(got) >= n:
                done.set()

        b.register_message_receive_handler("codec_probe", on_probe)
        a.run(background=True)
        b.run(background=True)
        snap0 = mx.snapshot()["counters"]
        try:
            for i in range(n):
                a.send_message(
                    Message("codec_probe", 0, 1)
                    .add("i", i).add("model_params", {"w": payloads[i]}))
            ok = done.wait(timeout=15)
            _t.sleep(0.1)   # let straggling duplicates land (dedup check)
            if not ok:
                raise TimeoutError(
                    f"delivered {len(got)}/{n} compressed frames under "
                    "injected faults")
            if any(len(v) != 1 for v in got.values()):
                raise ValueError("exactly-once violated: a compressed "
                                 "frame was dispatched twice")
            # decoded == sender-side reconstruction, pinned bitwise
            # (codec_probe is not an anchored model stream -> absolute
            # sparse mode, reference = decode(encode(.)))
            for i in range(n):
                want = decode_sparse(encode_sparse(payloads[i], 0.25))
                if not _np.array_equal(got[i][0], want):
                    raise ValueError(f"payload {i}: decoded != encoded "
                                     "reconstruction")
            snap1 = mx.snapshot()["counters"]
            raw = snap1.get("comm.codec.loopback.bytes_raw", 0) \
                - snap0.get("comm.codec.loopback.bytes_raw", 0)
            wire_b = snap1.get("comm.codec.loopback.bytes_wire", 0) \
                - snap0.get("comm.codec.loopback.bytes_wire", 0)
            if not (0 < wire_b < raw):
                raise ValueError(
                    f"no payload reduction: raw={raw} wire={wire_b}")
            return {"delivered": n, "bytes_raw": raw, "bytes_wire": wire_b,
                    "reduction_x": round(raw / wire_b, 2)}
        finally:
            a.stop()
            b.stop()
            release_router(run)

    def live_loop_smoke():
        # the closed production loop end-to-end (ISSUE 15): a 3-round
        # miniature live loop — 1 silo client federated-training LoRA
        # adapters, 1 paged-engine replica serving them behind the
        # gateway, loadgen at low rate, ONE trainer kill (the server is
        # SIGKILL-severed after round 1 and resumes from checkpoint) —
        # must complete with the fleet hot-swapped to the final round's
        # version and ZERO non-2xx responses (shed 429s excluded),
        # inside a ~20s budget.
        import tempfile
        import time as _t

        from .comm.chaos import FaultSpec
        from .soak.loadgen import TrafficSpec
        from .soak.loop import LiveLoopHarness

        t0 = _t.perf_counter()
        with tempfile.TemporaryDirectory() as store, \
                tempfile.TemporaryDirectory() as ckpt:
            h = LiveLoopHarness(
                rounds=3, n_clients=1, n_replicas=1, seed=0,
                store_dir=store, checkpoint_dir=ckpt,
                max_len=32, prefill_chunk=4,
                fault_spec=FaultSpec(silo_kill={0: 1}),
                traffic=TrafficSpec(
                    seed=0, vocab=32, rate_rps=8.0, duration_s=20.0,
                    stream_frac=0.3, prefix_len=6, suffix_len_max=8,
                    out_len_max=6))
            try:
                # a 2s post-convergence traffic tail: the 3 training
                # rounds finish fast, and the zero-non-2xx bar should
                # cover steady-state serving too, not 3 requests
                rep = h.run(timeout=60, tail_s=2.0)
            finally:
                h.close()
        dt = _t.perf_counter() - t0
        if rep["non2xx_excl_shed"]:
            raise ValueError(
                f"live loop dropped requests: {rep['non2xx_excl_shed']} "
                f"non-2xx (codes {rep['error_codes']}) — shed 429s "
                "excluded, so these are real failures")
        if not rep["train_done"] or rep["train_error"]:
            raise RuntimeError(
                f"training did not complete: {rep['train_error']}")
        if rep["fleet_version"] != 3 or not rep["converged"]:
            raise ValueError(
                f"fleet never reached the final round's adapters: "
                f"fleet_version {rep['fleet_version']} (want 3), "
                f"versions {rep['fleet_versions']}")
        if len(rep["kills_executed"]) != 1:
            raise ValueError(
                f"trainer kill never fired: {rep['kills_executed']}")
        if dt > 20:
            raise RuntimeError(
                f"live loop smoke took {dt:.1f}s (budget 20s) — the "
                "probe is too slow for the diagnosis battery")
        return {"rounds": rep["rounds_done"],
                "requests": rep["requests"], "ok_requests": rep["ok"],
                "shed_429s": rep["shed_429s"], "non_2xx": 0,
                "fleet_version": rep["fleet_version"],
                "lag_max": rep["lag_max_seen"],
                "kills": rep["kills_executed"],
                "elapsed_s": round(dt, 1)}

    def attribution_smoke():
        # the attribution plane end-to-end (ISSUE 17): a tiny tracked
        # round program + loopback comm traffic + a small decode engine,
        # then all three legs checked — the XLA ledger's KV-pool bytes
        # agree with the engine's own serving.kv_bytes_per_slot math
        # within 1%, the round-time budget renders with transport share
        # > 0, and a forced error burst fires the fast-burn SLO alert —
        # inside a ~20s budget.
        import os as _os
        import time as _t

        import jax as _jax
        import jax.numpy as _jnp

        from .comm.manager import FedCommManager, create_transport
        from .comm.message import Message
        from .serving.engine import DecodeEngine
        from .llm.transformer import TransformerLM
        from .utils import metrics as mx
        from .utils import xla_ledger
        from .utils.attribution import attribute, render_table, \
            rows_from_recorder
        from .utils.events import recorder
        from .utils.slo import SloMonitor, default_specs

        t0 = _t.perf_counter()
        # leg a: a tracked program the ledger must capture, inside a
        # round-tagged span so the budget gets a round window
        f = mx.track_jit(_jax.jit(lambda a, b: a @ b), "probe_matmul")
        with recorder.span("train", round=0):
            x = _jnp.ones((64, 64))
            f(x, x).block_until_ready()
        prog = xla_ledger.programs().get("probe_matmul", {})
        if not prog.get("flops"):
            raise ValueError(
                f"xla ledger captured no cost analysis: {prog!r}")
        # comm traffic -> transport share; loopback manager stamps
        # backend meta on the send/handle spans
        run = f"diag-attr-{_os.getpid()}"
        a = FedCommManager(create_transport("loopback", 0, run), rank=0)
        b = FedCommManager(create_transport("loopback", 1, run), rank=1)
        got = []
        b.register_message_receive_handler(
            "probe", lambda m: got.append(m))
        b.run(background=True)
        for _ in range(3):
            a.send_message(Message("probe", 0, 1))
        deadline = _t.monotonic() + 5
        while len(got) < 3 and _t.monotonic() < deadline:
            _t.sleep(0.01)
        a.stop()
        b.stop()
        if len(got) != 3:
            raise RuntimeError(f"loopback delivered {len(got)}/3")
        # leg a (memory): engine HBM ledger vs the engine's own math
        model = TransformerLM(vocab_size=32, d_model=16, n_layers=1,
                              n_heads=2, d_ff=32, scan_layers=True)
        params = model.init(_jax.random.key(0),
                            _jnp.zeros((1, 8), _jnp.int32))["params"]
        eng = DecodeEngine(model, params, n_slots=2, max_len=32).start()
        try:
            eng.submit([1, 2, 3], 4).result(timeout=30)
        finally:
            eng.stop()
        ledger_kv = xla_ledger.buffers().get("kv_pool", 0)
        engine_kv = 2 * mx.registry.gauge(
            "serving.kv_bytes_per_slot").value()
        if not engine_kv or abs(ledger_kv - engine_kv) / engine_kv > 0.01:
            raise ValueError(
                f"KV ledger disagrees with the engine: ledger {ledger_kv} "
                f"vs engine {engine_kv} (must agree within 1%)")
        # leg b: budget renders, transport was in flight
        att = attribute(rows_from_recorder())
        table = render_table(att)
        share = att["totals"]["transport_share"]
        if "transport%" not in table or share <= 0:
            raise ValueError(
                f"budget table missing transport share: {share} "
                f"(table: {table.splitlines()[0]!r})")
        # leg c: a forced error burst must fire the fast-burn alert —
        # private registry + injected clock, so the burst is deterministic
        reg = mx.MetricsRegistry()
        clock = [0.0]
        mon = SloMonitor(default_specs(), fast_window_s=5.0,
                         time_fn=lambda: clock[0], registry=reg)
        reg.counter("loadgen.ok").inc(100)
        mon.sample()
        clock[0] = 1.0
        reg.counter("loadgen.errors").inc(50)
        mon.sample()
        if "availability.fast" not in mon.firing():
            raise ValueError(
                f"forced error burst did not fire the fast-burn alert: "
                f"firing={mon.firing()}")
        dt = _t.perf_counter() - t0
        if dt > 20:
            raise RuntimeError(
                f"attribution smoke took {dt:.1f}s (budget 20s)")
        return {"program_flops": prog.get("flops"),
                "kv_ledger_bytes": ledger_kv,
                "kv_engine_bytes": engine_kv,
                "transport_share": share,
                "alerts_firing": mon.firing(),
                "elapsed_s": round(dt, 1)}

    def fleet_obs_smoke():
        # the fleet-observability plane end-to-end (ISSUE 18): three REAL
        # child processes — two gRPC peers exchanging reliable traffic
        # both ways and one victim — scraped by a FleetCollector into one
        # aggregated /metrics carrying three `process` label values, the
        # peers' traces merged into one clock-corrected timeline with >=1
        # stitched send->handle flow and ZERO ordering violations, and
        # the victim SIGKILLed mid-heartbeat leaving a readable
        # postmortem naming its last span — inside a ~20s budget.
        import os as _os
        import signal as _sig
        import socket as _socket
        import subprocess as _sp
        import tempfile as _tf
        import threading as _th
        import time as _t

        from .utils.obsfleet import (FleetCollector, load_trace,
                                     merge_traces, verify_merged_order)
        from .utils.postmortem import POSTMORTEM_FILE, load_postmortem
        from .utils.prometheus import parse_prometheus, split_by_label

        t0 = _t.perf_counter()

        def free_port():
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        root = _os.path.dirname(_os.path.dirname(_os.path.abspath(
            __file__)))
        # the children are jax-free by construction; JAX_PLATFORMS=cpu
        # makes sure of it where this process holds a chip — a child that
        # ever initialised a backend there would hang on the held device
        env = {**_os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": _os.pathsep.join(
                   [root] + ([_os.environ["PYTHONPATH"]]
                             if _os.environ.get("PYTHONPATH") else []))}
        pa, pb = free_port(), free_port()

        def spawn(src):
            return _sp.Popen([sys.executable, "-c", src], env=env,
                             stdin=_sp.PIPE, stdout=_sp.PIPE,
                             stderr=_sp.PIPE, text=True)

        def ready_line(p, timeout=30):
            out: list = []
            th = _th.Thread(
                target=lambda: out.append(p.stdout.readline()),
                daemon=True)
            th.start()
            th.join(timeout)
            if not out or not out[0]:
                err = (p.stderr.read()[-400:]
                       if p.poll() is not None else "(still running)")
                raise TimeoutError(f"child never reported ready: {err}")
            return json.loads(out[0])

        n = 4
        with _tf.TemporaryDirectory() as d:
            tr_a = _os.path.join(d, "a.trace.json")
            tr_b = _os.path.join(d, "b.trace.json")
            victim_dir = _os.path.join(d, "victim")
            procs = [
                spawn(_FLEET_PEER_SRC.format(
                    rank=0, n=n, port_a=pa, port_b=pb, my_port=pa,
                    trace=tr_a)),
                spawn(_FLEET_PEER_SRC.format(
                    rank=1, n=n, port_a=pa, port_b=pb, my_port=pb,
                    trace=tr_b)),
                spawn(_FLEET_VICTIM_SRC.format(run_dir=victim_dir))]
            try:
                ready = [ready_line(p) for p in procs]
                if not (ready[0]["ok"] and ready[1]["ok"]):
                    raise RuntimeError(f"peer exchange failed: {ready[:2]}")
                coll = FleetCollector({"peer_a": ready[0]["url"],
                                       "peer_b": ready[1]["url"],
                                       "victim": ready[2]["url"]})
                ok = coll.scrape_once()
                if not all(ok.values()):
                    raise RuntimeError(f"scrape failed: {ok}")
                agg = parse_prometheus(coll.aggregated_text())
                per = {k: v for k, v in
                       split_by_label(agg, "process").items() if k}
                if sorted(per) != ["peer_a", "peer_b", "victim"]:
                    raise ValueError("aggregated /metrics missing process "
                                     f"labels: {sorted(per)}")
                vs = per["victim"]["counters"].get("victim_steps_total")
                if not vs:
                    raise ValueError("victim counter absent from the "
                                     "aggregated view")
                # the victim's inflight spill must exist BEFORE the kill —
                # SIGKILL runs no handler, the spill is all that survives
                pm_path = _os.path.join(victim_dir, POSTMORTEM_FILE)
                deadline = _t.monotonic() + 10
                while (not _os.path.exists(pm_path)
                       and _t.monotonic() < deadline):
                    _t.sleep(0.02)
                if not _os.path.exists(pm_path):
                    raise TimeoutError(
                        "victim never spilled an inflight postmortem")
                procs[2].send_signal(_sig.SIGKILL)
                procs[2].wait(timeout=10)
                coll.scrape_once()     # dead endpoint -> stale mark
                fsnap = coll.fleet_snapshot()
                if not fsnap["processes"]["victim"]["stale"]:
                    raise ValueError("SIGKILLed victim not marked stale")
                pm = load_postmortem(victim_dir)
                if pm is None or "hard-kill" not in pm["reason"]:
                    raise ValueError("postmortem unreadable or wrong "
                                     f"reason: {pm and pm.get('reason')}")
                if not str(pm["last_span"] or "").startswith("victim."):
                    raise ValueError(
                        f"postmortem last span {pm['last_span']!r}")
                for p in procs[:2]:    # peers exit when stdin closes
                    p.stdin.close()
                for p in procs[:2]:
                    p.wait(timeout=15)
                res = merge_traces(
                    [("peer_a", tr_a), ("peer_b", tr_b)],
                    out_path=_os.path.join(d, "merged.trace.json"))
                if res["flows"] < 1:
                    raise ValueError(
                        f"no stitched send->handle flow: {res}")
                bad = verify_merged_order(load_trace(res["out"]))
                if bad:
                    raise ValueError(
                        f"{bad} flow(s) violate corrected ordering")
            finally:
                for p in procs:
                    if p.poll() is None:
                        p.kill()
        dt = _t.perf_counter() - t0
        if dt > 20:
            raise RuntimeError(
                f"fleet obs smoke took {dt:.1f}s (budget 20s)")
        return {"processes": sorted(per), "victim_steps": int(vs),
                "flows": res["flows"], "order_violations": 0,
                "clock_skew_ms": res["clock_skew_ms"],
                "clamped": res["clamped"],
                "postmortem_reason": pm["reason"],
                "last_span": pm["last_span"], "elapsed_s": round(dt, 1)}

    probes = {"jax": jax_devices, "wire_codec": wire,
              "loopback_transport": loopback, "grpc_transport": grpc,
              "native_lib": native, "metrics_endpoint": metrics_endpoint,
              "chaos_smoke": chaos_smoke, "codec_smoke": codec_smoke,
              "serving_engine_smoke": serving_engine_smoke,
              "serving_paged_smoke": serving_paged_smoke,
              "serving_spec_smoke": serving_spec_smoke,
              "serving_density_smoke": serving_density_smoke,
              "fleet_rolling_update_smoke": fleet_rolling_update_smoke,
              "partition_rules_smoke": partition_rules_smoke,
              "cohort_sharded_smoke": cohort_sharded_smoke,
              "cross_silo_durability_smoke": cross_silo_durability_smoke,
              "live_loop_smoke": live_loop_smoke,
              "attribution_smoke": attribution_smoke,
              "fleet_obs_smoke": fleet_obs_smoke,
              "lint_clean": lint_clean}
    required = ("jax", "wire_codec", "loopback_transport", "chaos_smoke",
                "codec_smoke",
                "serving_engine_smoke", "serving_paged_smoke",
                "serving_spec_smoke", "serving_density_smoke",
                "fleet_rolling_update_smoke",
                "partition_rules_smoke", "cohort_sharded_smoke",
                "cross_silo_durability_smoke", "live_loop_smoke",
                "attribution_smoke", "fleet_obs_smoke", "lint_clean")
    # --only: run a subset by name — a failing fleet probe can be re-run
    # in seconds instead of paying the full battery every iteration
    selected = getattr(args, "only", None) or list(probes)
    unknown = sorted(set(selected) - set(probes))
    if unknown:
        print(f"unknown probe(s) {unknown}; available: {sorted(probes)}",
              file=sys.stderr)
        return 2
    for name in probes:
        if name in selected:
            check(name, probes[name])
    required_ok = all(checks[k]["ok"] for k in required if k in checks)
    print(json.dumps({"ok": required_ok, "checks": checks}, indent=2))
    return 0 if required_ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="fedml_tpu",
        description="TPU-native federated learning (reference CLI: fedml)")
    sub = p.add_subparsers(dest="cmd", required=True)
    sub.add_parser("version", help="print the version")
    sub.add_parser("env", help="report the runtime environment")
    runp = sub.add_parser("run", help="run a fedml_config.yaml")
    runp.add_argument("--cf", "--config", dest="config", required=True,
                      help="path to config yaml (reference-format accepted)")
    runp.add_argument("--rounds", type=int, default=None,
                      help="override comm_round")
    sub.add_parser("bench", help="run the repo benchmark (bench.py)")
    lp = sub.add_parser("launch", help="submit a job spec to the scheduler")
    lp.add_argument("job", help="job spec yaml/json (scheduler spec)")
    lp.add_argument("--store", default=None,
                    help="sqlite path for a durable job queue")
    lp.add_argument("--timeout", type=float, default=600.0)
    bp = sub.add_parser("build", help="package a job dir into a tarball")
    bp.add_argument("--source", required=True, help="job directory")
    bp.add_argument("--entry", default=None, help="entry file inside source")
    bp.add_argument("--dest", default="./dist", help="output directory")
    bp.add_argument("--name", default=None, help="package name")
    gp = sub.add_parser("logs", help="show per-run logs/events")
    gp.add_argument("--log-dir", default="./log")
    gp.add_argument("--run", default=None, help="run-name prefix filter")
    gp.add_argument("--tail", type=int, default=50)
    gp.add_argument("--list", action="store_true", help="list runs only")
    dp = sub.add_parser("diagnosis",
                        help="transport/device connectivity checks")
    dp.add_argument("--only", nargs="+", default=None, metavar="PROBE",
                    help="run only the named probe(s) — e.g. "
                         "`diagnosis --only chaos_smoke` re-checks one "
                         "failing probe without the full battery")
    lint_p = sub.add_parser(
        "lint", help="graftlint: repo-native static analysis "
                     "(donation/retrace/knob/metric/lock/purity rules)")
    lint_p.add_argument("paths", nargs="*", default=None,
                        help="files/dirs to scan (default: the fedml_tpu "
                             "package tree)")
    lint_p.add_argument("--format", choices=("text", "json"),
                        default="text",
                        help="json emits the stable CI schema")
    lint_p.add_argument("--rules", default=None,
                        help="comma-separated rule subset (see "
                             "--list-rules)")
    lint_p.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    rp = sub.add_parser("report",
                        help="summarize a tracked run's telemetry "
                             "(spans, counters, trace pointer)")
    rp.add_argument("--events", default=None,
                    help="path to a <run>.events.jsonl (overrides "
                         "--log-dir/--run)")
    rp.add_argument("--log-dir", default="./log")
    rp.add_argument("--run", default=None, help="run-name prefix filter")
    rp.add_argument("--format", choices=("text", "json"), default="text",
                    help="json emits one stable machine-readable object "
                         "(budget table, SLO/alert summary, metrics "
                         "snapshot) for CI/autoscaler consumption")
    rp.add_argument("--merge", nargs="+", default=None, metavar="RUN_DIR",
                    help="merge N run dirs' (or *.trace.json files') "
                         "Chrome traces into ONE clock-corrected Perfetto "
                         "timeline with cross-process send->handle flow "
                         "arrows; exits 1 if a recv still precedes its "
                         "corrected send")
    rp.add_argument("--out", default=None,
                    help="--merge output path (default merged.trace.json)")
    rp.add_argument("--fleet", default=None, metavar="URL",
                    help="FleetCollector URL (or saved /fleet JSON file): "
                         "fold the live fleet snapshot — per-process "
                         "columns, fleet sums, staleness marks — into "
                         "the report")
    tp = sub.add_parser("top",
                        help="live one-screen run health from a /metrics "
                             "endpoint (or a finished run's events file)")
    tp.add_argument("--url", default=None,
                    help="…/metrics endpoint URL of a live run "
                         "(common_args.extra.metrics_port)")
    tp.add_argument("--port", type=int, default=None,
                    help="shorthand for --url http://127.0.0.1:PORT/metrics")
    tp.add_argument("--log-dir", default="./log",
                    help="fallback: newest run's end-of-run snapshot here")
    tp.add_argument("--run", default=None, help="run-name prefix filter")
    tp.add_argument("--interval", type=float, default=2.0,
                    help="seconds between frames")
    tp.add_argument("--once", action="store_true",
                    help="render one frame and exit")
    tp.add_argument("--frames", type=int, default=0,
                    help="stop after N frames (0 = run until ^C)")
    tp.add_argument("--fleet", action="store_true",
                    help="treat --url/--port as a FleetCollector's "
                         "AGGREGATED /metrics and render per-process "
                         "columns instead of the single-process frame")
    args = p.parse_args(argv)
    return {"version": cmd_version, "env": cmd_env, "run": cmd_run,
            "bench": cmd_bench, "launch": cmd_launch, "build": cmd_build,
            "logs": cmd_logs, "diagnosis": cmd_diagnosis, "lint": cmd_lint,
            "report": cmd_report, "top": cmd_top}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
