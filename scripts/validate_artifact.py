#!/usr/bin/env python
"""Schema lint for committed measurement artifacts.

Every BENCH_*/TUNE_*/PROFILE_*/TRACE_*/FLIGHT_* JSON in the repo root
is part of the evidence chain the round-end driver and the scaling
regeneration consume — a truncated or key-drifted artifact fails SILENTLY there
(rows skipped, resume identity never matching, `complete` read as
falsy).  This linter makes the contract explicit and cheap to check:

  * the file parses as JSON — or as JSON-LINES (one metric record
    per line);
  * supervisor records (BENCH_r<round>*.json: {'n','cmd','rc',...})
    carry their replay keys;
  * row-carrying artifacts carry a boolean ``complete`` (the resumable
    contract: false until the final flush), a platform tag
    (``platform`` or ``inner_platform`` — rows without one can be
    mistaken for chip numbers), and a list-of-dicts ``rows`` section;
  * TRACE_* files must satisfy the Chrome trace-event contract
    (delegated to scripts/validate_trace.py);
  * FLIGHT_* incident bundles must carry every correlated section
    (spans, timeseries, state, diagnose_tpu, ...) and ``complete``;
  * anything else must at least self-identify with a ``metric`` key.

Usage:
    python scripts/validate_artifact.py            # lint the repo root
    python scripts/validate_artifact.py FILE...    # lint specific files

Exit 0 when every artifact passes, 1 otherwise (missing files named on
the command line are an error; an empty repo-root glob is not).
"""
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: repo-root artifact families under the resumable-measurement contract
PATTERNS = ("BENCH_*.json", "TUNE_*.json", "PROFILE_*.json",
            "TRACE_*.json", "FLIGHT_*.json",
            os.path.join("flight", "FLIGHT_*.json"))

#: FlightRecorder bundle contract (bigdl_tpu.obs.flight._dump): every
#: key must be present — a partial bundle means the dump died mid-write
#: and the forensic evidence cannot be trusted
FLIGHT_KEYS = ("flight", "ts_unix", "ts", "detail", "spans",
               "active_requests", "timeseries", "state", "registry",
               "diagnose_tpu", "complete")


def _flight_problems(doc) -> list:
    """FLIGHT_*.json: the incident bundle is correlated evidence (spans
    + time-series window + diagnostics captured at one instant) — it
    has neither ``rows`` nor ``metric``, so it gets its own contract."""
    probs = []
    if not isinstance(doc, dict):
        return ["flight bundle top level is %s, expected object"
                % type(doc).__name__]
    for k in FLIGHT_KEYS:
        if k not in doc:
            probs.append("flight bundle lacks %r" % k)
    if doc.get("complete") is not True:
        probs.append("flight bundle 'complete' must be true "
                     "(bundles are written atomically or not at all)")
    if "spans" in doc:
        spans = doc["spans"]
        if not isinstance(spans, list):
            probs.append("'spans' is not a list")
        elif not all(isinstance(s, dict) for s in spans):
            probs.append("'spans' holds non-object entries")
    if "timeseries" in doc and not isinstance(doc["timeseries"], list):
        probs.append("'timeseries' is not a list")
    if "state" in doc and not isinstance(doc["state"], dict):
        probs.append("'state' is not an object")
    if "active_requests" in doc \
            and not isinstance(doc["active_requests"], dict):
        probs.append("'active_requests' is not an object")
    return probs


def _trace_problems(path: str) -> list:
    """TRACE_*.json delegates to validate_trace (Chrome trace-event
    contract: known phases, ts/dur present, monotonic-safe)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from validate_trace import validate_trace
    finally:
        sys.path.pop(0)
    return validate_trace(path)


def _mesh_problems(doc) -> list:
    """BENCH_MESH.json extras: the mesh-sliced serving proof is an
    AGREEMENT artifact — a row without its agreement fraction (or with
    one outside [0, 1]) is not evidence, and a complete doc must carry
    the summary the round-end driver reads (``agreement_min``)."""
    probs = []
    if doc.get("error"):
        return probs  # degraded-run marker (e.g. < 4 devices) is valid
    for i, r in enumerate(doc.get("rows", [])):
        if not isinstance(r, dict):
            continue
        if "stage" not in r:
            probs.append("mesh row %d lacks a 'stage' key" % i)
        a = r.get("agreement")
        if not isinstance(a, (int, float)) or not 0.0 <= a <= 1.0:
            probs.append("mesh row %d: 'agreement' must be a fraction "
                         "in [0, 1], got %r" % (i, a))
    if doc.get("complete") is True:
        summ = doc.get("summary")
        if not isinstance(summ, dict) or "agreement_min" not in summ:
            probs.append("complete mesh artifact lacks "
                         "summary.agreement_min")
    return probs


def _spec_problems(doc) -> list:
    """BENCH_SPEC.json extras: the speculative-decoding proof is only
    evidence if the spec stream IS the offline trajectory — a complete
    doc must carry summary.agreement == 1.0 and a measured acceptance
    rate in [0, 1]; any speedup number without those is noise."""
    probs = []
    if doc.get("error"):
        return probs
    for i, r in enumerate(doc.get("rows", [])):
        if not isinstance(r, dict):
            continue
        if "stage" not in r:
            probs.append("spec row %d lacks a 'stage' key" % i)
    if doc.get("complete") is True:
        summ = doc.get("summary")
        if not isinstance(summ, dict):
            probs.append("complete spec artifact lacks a summary")
            return probs
        if summ.get("agreement") != 1.0:
            probs.append("complete spec artifact: summary.agreement "
                         "must be exactly 1.0, got %r"
                         % (summ.get("agreement"),))
        a = summ.get("acceptance_rate")
        if not isinstance(a, (int, float)) or not 0.0 <= a <= 1.0:
            probs.append("complete spec artifact: "
                         "summary.acceptance_rate must be a fraction "
                         "in [0, 1], got %r" % (a,))
    return probs


def _spec2_problems(doc) -> list:
    """BENCH_SPEC2.json extras: the Speculation 2.0 duel is only
    evidence when EVERY arm streamed the offline trajectory
    (agreement exactly 1.0 per row) and carries a numeric
    accepted-tokens-per-verify-step — the equal-budget comparison
    metric — plus a verify-executable count matching its ladder (the
    bounded-compile contract the tree rides on)."""
    probs = []
    if doc.get("error"):
        return probs
    for i, r in enumerate(doc.get("rows", [])):
        if not isinstance(r, dict):
            continue
        if "stage" not in r:
            probs.append("spec2 row %d lacks a 'stage' key" % i)
        if doc.get("complete") is True:
            if r.get("agreement") != 1.0:
                probs.append("complete spec2 artifact: row %d (%s) "
                             "agreement must be exactly 1.0, got %r"
                             % (i, r.get("stage"), r.get("agreement")))
            aps = r.get("accepted_per_verify_step")
            if not isinstance(aps, (int, float)):
                probs.append("complete spec2 artifact: row %d (%s) "
                             "lacks numeric accepted_per_verify_step"
                             % (i, r.get("stage")))
            if r.get("verify_compiles") != r.get(
                    "expected_verify_compiles"):
                probs.append("complete spec2 artifact: row %d (%s) "
                             "verify_compiles %r != expected %r (one "
                             "donated executable per ladder rung)"
                             % (i, r.get("stage"), r.get("verify_compiles"),
                                r.get("expected_verify_compiles")))
    if doc.get("complete") is True:
        summ = doc.get("summary")
        if not isinstance(summ, dict):
            probs.append("complete spec2 artifact lacks a summary")
            return probs
        tb = summ.get("tree_beats_linear")
        if not isinstance(tb, dict) or not any(tb.values()):
            probs.append("complete spec2 artifact: "
                         "summary.tree_beats_linear must hold on >= 1 "
                         "trace family, got %r" % (tb,))
        if summ.get("ngram_beats_model") is not True:
            probs.append("complete spec2 artifact: "
                         "summary.ngram_beats_model must be true, got %r"
                         % (summ.get("ngram_beats_model"),))
    return probs


def _disagg_problems(doc) -> list:
    """BENCH_DISAGG.json extras: the disaggregated-serving proof is an
    AGREEMENT artifact — every stage must stream the exact co-located
    trajectory (agreement == 1.0) or the latency numbers are comparing
    different computations.  A complete doc must also carry the per-stage
    tail latencies the round-end driver reads."""
    probs = []
    if doc.get("error"):
        return probs
    for i, r in enumerate(doc.get("rows", [])):
        if not isinstance(r, dict):
            continue
        if "stage" not in r:
            probs.append("disagg row %d lacks a 'stage' key" % i)
        if doc.get("complete") is True:
            if r.get("agreement") != 1.0:
                probs.append("complete disagg artifact: row %d (%s) "
                             "agreement must be exactly 1.0, got %r"
                             % (i, r.get("stage"), r.get("agreement")))
            if not isinstance(r.get("itl_p99_ms"), (int, float)):
                probs.append("complete disagg artifact: row %d (%s) "
                             "lacks numeric itl_p99_ms"
                             % (i, r.get("stage")))
            ttft = r.get("ttft")
            if (not isinstance(ttft, dict)
                    or not isinstance(ttft.get("p99_ms"), (int, float))):
                probs.append("complete disagg artifact: row %d (%s) "
                             "lacks numeric ttft.p99_ms"
                             % (i, r.get("stage")))
    if doc.get("complete") is True:
        summ = doc.get("summary")
        if not isinstance(summ, dict):
            probs.append("complete disagg artifact lacks a summary")
            return probs
        for key in ("itl_p99_ms", "ttft_p99_ms", "agreement"):
            if not isinstance(summ.get(key), dict):
                probs.append("complete disagg artifact: summary.%s "
                             "must map stage -> value" % key)
        ags = summ.get("agreement")
        if isinstance(ags, dict) and any(v != 1.0 for v in ags.values()):
            probs.append("complete disagg artifact: summary.agreement "
                         "must be exactly 1.0 for every stage, got %r"
                         % (ags,))
        if summ.get("chaos_zero_accepted_loss") is not True:
            probs.append("complete disagg artifact: "
                         "summary.chaos_zero_accepted_loss must be true")
    return probs


def _qcompute_problems(doc) -> list:
    """BENCH_QCOMPUTE.json extras: the int8-compute proof has two row
    families — ``duel:*`` kernel-duel rows (must carry a numeric
    ``step_s``; a non-numeric duel row means the autotune verdict the
    ``spec_auto`` stage traced against was never measured) and serving
    stages, where every ``spec_*`` replay stage must stream the offline
    trajectory exactly (agreement == 1.0 — drafter numerics must never
    reach the emitted stream, whatever kernels it runs)."""
    probs = []
    if doc.get("error"):
        return probs
    for i, r in enumerate(doc.get("rows", [])):
        if not isinstance(r, dict):
            continue
        stage = r.get("stage")
        if stage is None:
            probs.append("qcompute row %d lacks a 'stage' key" % i)
            continue
        if str(stage).startswith("duel:"):
            if not isinstance(r.get("step_s"), (int, float)):
                probs.append("qcompute duel row %d (%s) lacks numeric "
                             "step_s" % (i, stage))
        elif str(stage).startswith("spec_"):
            if doc.get("complete") is True \
                    and r.get("agreement") != 1.0:
                probs.append("complete qcompute artifact: row %d (%s) "
                             "agreement must be exactly 1.0, got %r"
                             % (i, stage, r.get("agreement")))
            a = r.get("accept_rate")
            if a is not None and (not isinstance(a, (int, float))
                                  or not 0.0 <= a <= 1.0):
                probs.append("qcompute row %d (%s): 'accept_rate' must "
                             "be a fraction in [0, 1], got %r"
                             % (i, stage, a))
    if doc.get("complete") is True:
        summ = doc.get("summary")
        if not isinstance(summ, dict):
            probs.append("complete qcompute artifact lacks a summary")
            return probs
        if summ.get("agreement") not in (1.0, None):
            probs.append("complete qcompute artifact: summary.agreement "
                         "must be exactly 1.0 (or null when unprobed), "
                         "got %r" % (summ.get("agreement"),))
        if not isinstance(summ.get("auto_verdicts"), dict):
            probs.append("complete qcompute artifact lacks "
                         "summary.auto_verdicts (the duel outcomes "
                         "'auto' traced against)")
    return probs


def _kvtier_problems(doc) -> list:
    """BENCH_KVTIER.json extras: a memory tier must be invisible to
    the sampler — the hibernate_exact stage's agreement must be
    exactly 1.0 in a complete artifact (a resumed stream that diverges
    by one token is corruption, not a miss).  A complete doc must also
    show the tier actually working: a nonzero oversubscribed-stage
    prefix hit rate and a TTFT-on-resume that beat the engine's own
    re-prefill + replay fallback."""
    probs = []
    if doc.get("error"):
        return probs
    rows = {r.get("stage"): r for r in doc.get("rows", [])
            if isinstance(r, dict)}
    for i, r in enumerate(doc.get("rows", [])):
        if isinstance(r, dict) and "stage" not in r:
            probs.append("kvtier row %d lacks a 'stage' key" % i)
    if doc.get("complete") is not True:
        return probs
    hib = rows.get("hibernate_exact")
    if not isinstance(hib, dict) or hib.get("agreement") != 1.0:
        probs.append("complete kvtier artifact: hibernate_exact "
                     "agreement must be exactly 1.0, got %r"
                     % ((hib or {}).get("agreement"),))
    over = rows.get("oversubscribed")
    if not isinstance(over, dict) or not over.get("prefix_hit_rate"):
        probs.append("complete kvtier artifact: oversubscribed "
                     "prefix_hit_rate must be nonzero, got %r"
                     % ((over or {}).get("prefix_hit_rate"),))
    summ = doc.get("summary")
    if not isinstance(summ, dict):
        probs.append("complete kvtier artifact lacks a summary")
        return probs
    if summ.get("agreement") != 1.0:
        probs.append("complete kvtier artifact: summary.agreement "
                     "must be exactly 1.0, got %r"
                     % (summ.get("agreement"),))
    for key in ("ttft_resume_ms", "ttft_reprefill_ms",
                "prefix_hit_rate"):
        if not isinstance(summ.get(key), (int, float)):
            probs.append("complete kvtier artifact: summary.%s must "
                         "be numeric, got %r" % (key, summ.get(key)))
    return probs


def _router_problems(doc) -> list:
    """BENCH_ROUTER.json extras: routing is only evidence when it (a)
    never changed an output — agreement must be exactly 1.0 on every
    stage — and (b) actually beat the radix-blind baseline on set-level
    prefix hit rate.  The chaos stage must show zero accepted-request
    loss: a replica died mid-trace and every stream still finished,
    re-routed, bit-exact."""
    probs = []
    if doc.get("error"):
        return probs
    rows = {r.get("stage"): r for r in doc.get("rows", [])
            if isinstance(r, dict)}
    for i, r in enumerate(doc.get("rows", [])):
        if isinstance(r, dict) and "stage" not in r:
            probs.append("router row %d lacks a 'stage' key" % i)
    if doc.get("complete") is not True:
        return probs
    for stage in ("blind", "routed", "chaos"):
        r = rows.get(stage)
        if not isinstance(r, dict) or r.get("agreement") != 1.0:
            probs.append("complete router artifact: %s agreement must "
                         "be exactly 1.0, got %r"
                         % (stage, (r or {}).get("agreement")))
    blind, routed = rows.get("blind") or {}, rows.get("routed") or {}
    bh, rh = blind.get("prefix_hit_rate"), routed.get("prefix_hit_rate")
    if not (isinstance(bh, (int, float)) and isinstance(rh, (int, float))
            and rh > bh):
        probs.append("complete router artifact: routed prefix_hit_rate "
                     "must be strictly above blind, got routed=%r "
                     "blind=%r" % (rh, bh))
    chaos = rows.get("chaos") or {}
    if chaos.get("accepted_loss") != 0:
        probs.append("complete router artifact: chaos accepted_loss "
                     "must be exactly 0, got %r"
                     % (chaos.get("accepted_loss"),))
    summ = doc.get("summary")
    if not isinstance(summ, dict):
        probs.append("complete router artifact lacks a summary")
        return probs
    if summ.get("agreement") != 1.0:
        probs.append("complete router artifact: summary.agreement must "
                     "be exactly 1.0, got %r" % (summ.get("agreement"),))
    if summ.get("chaos_zero_accepted_loss") is not True:
        probs.append("complete router artifact: "
                     "summary.chaos_zero_accepted_loss must be true, "
                     "got %r" % (summ.get("chaos_zero_accepted_loss"),))
    for key in ("ttft_p50_ms", "ttft_p99_ms"):
        v = summ.get(key)
        if not (isinstance(v, dict)
                and isinstance(v.get("blind"), (int, float))
                and isinstance(v.get("routed"), (int, float))):
            probs.append("complete router artifact: summary.%s must "
                         "report numeric blind+routed arms, got %r"
                         % (key, v))
    return probs


def _deadline_problems(doc) -> list:
    """BENCH_DEADLINE.json extras: the lifecycle machinery is only
    evidence when (a) it never changed a surviving token — agreement
    must be exactly 1.0 on every stage — (b) the chaos stage (client
    disconnect storm + replica kill mid-hedge) lost zero accepted
    requests, and (c) both arms report numeric wasted-decode and
    goodput so the strictly-better claims are checkable."""
    probs = []
    if doc.get("error"):
        return probs
    rows = {r.get("stage"): r for r in doc.get("rows", [])
            if isinstance(r, dict)}
    for i, r in enumerate(doc.get("rows", [])):
        if isinstance(r, dict) and "stage" not in r:
            probs.append("deadline row %d lacks a 'stage' key" % i)
    if doc.get("complete") is not True:
        return probs
    for stage in ("lifecycle", "baseline", "chaos"):
        r = rows.get(stage)
        if not isinstance(r, dict) or r.get("agreement") != 1.0:
            probs.append("complete deadline artifact: %s agreement "
                         "must be exactly 1.0, got %r"
                         % (stage, (r or {}).get("agreement")))
        if isinstance(r, dict) and r.get("accepted_loss") != 0:
            probs.append("complete deadline artifact: %s accepted_loss "
                         "must be exactly 0, got %r"
                         % (stage, r.get("accepted_loss")))
    lc = rows.get("lifecycle") or {}
    bl = rows.get("baseline") or {}
    lw, bw = lc.get("wasted_decode_steps"), bl.get("wasted_decode_steps")
    if not (isinstance(lw, int) and isinstance(bw, int) and lw < bw):
        probs.append("complete deadline artifact: lifecycle "
                     "wasted_decode_steps must be a strict int "
                     "improvement over baseline, got lifecycle=%r "
                     "baseline=%r" % (lw, bw))
    lg, bg = lc.get("goodput_rps"), bl.get("goodput_rps")
    if not (isinstance(lg, (int, float)) and isinstance(bg, (int, float))
            and lg > bg):
        probs.append("complete deadline artifact: lifecycle goodput_rps "
                     "must be strictly above baseline, got lifecycle=%r "
                     "baseline=%r" % (lg, bg))
    summ = doc.get("summary")
    if not isinstance(summ, dict):
        probs.append("complete deadline artifact lacks a summary")
        return probs
    if summ.get("agreement") != 1.0:
        probs.append("complete deadline artifact: summary.agreement "
                     "must be exactly 1.0, got %r"
                     % (summ.get("agreement"),))
    if summ.get("chaos_zero_accepted_loss") is not True:
        probs.append("complete deadline artifact: "
                     "summary.chaos_zero_accepted_loss must be true, "
                     "got %r" % (summ.get("chaos_zero_accepted_loss"),))
    for key in ("wasted_decode_steps", "goodput_rps"):
        v = summ.get(key)
        if not (isinstance(v, dict)
                and isinstance(v.get("lifecycle"), (int, float))
                and isinstance(v.get("baseline"), (int, float))):
            probs.append("complete deadline artifact: summary.%s must "
                         "report numeric lifecycle+baseline arms, "
                         "got %r" % (key, v))
    return probs


def _memprofile_problems(doc) -> list:
    """PROFILE_MEM.json extras: the memory-ledger profile is only
    evidence when the attribution actually happened — a complete doc
    must carry a nonempty subsystem->bytes attribution table, at least
    one executable cost row, and a numeric reconciliation drift (the
    CPU degrade path still reports drift_bytes == 0, never null)."""
    probs = []
    if doc.get("error"):
        return probs
    rows = {r.get("stage"): r for r in doc.get("rows", [])
            if isinstance(r, dict)}
    for i, r in enumerate(doc.get("rows", [])):
        if isinstance(r, dict) and "stage" not in r:
            probs.append("memprofile row %d lacks a 'stage' key" % i)
    if doc.get("complete") is not True:
        return probs
    attr = (rows.get("attribution") or {}).get("attribution")
    if not isinstance(attr, dict) or not attr:
        probs.append("complete memprofile artifact: attribution row "
                     "must carry a nonempty subsystem->bytes table, "
                     "got %r" % (attr,))
    elif not all(isinstance(v, (int, float)) for v in attr.values()):
        probs.append("complete memprofile artifact: attribution "
                     "values must be numeric byte counts")
    exe = rows.get("executables")
    if not isinstance(exe, dict) or not exe.get("rows"):
        probs.append("complete memprofile artifact: executables row "
                     "must carry at least one cost row")
    rec = rows.get("reconciliation")
    if not isinstance(rec, dict) \
            or not isinstance(rec.get("drift_bytes"), (int, float)) \
            or isinstance(rec.get("drift_bytes"), bool):
        probs.append("complete memprofile artifact: reconciliation "
                     "row must carry numeric drift_bytes, got %r"
                     % ((rec or {}).get("drift_bytes"),))
    elif rec.get("verdict") not in ("reconciled", "degraded"):
        probs.append("complete memprofile artifact: reconciliation "
                     "verdict must be 'reconciled' or 'degraded', "
                     "got %r" % (rec.get("verdict"),))
    summ = doc.get("summary")
    if not isinstance(summ, dict) \
            or not isinstance(summ.get("subsystems"), int):
        probs.append("complete memprofile artifact lacks "
                     "summary.subsystems")
    return probs


def _problems(doc, name: str = "") -> list:
    """Contract violations for one parsed artifact document."""
    probs = []
    if isinstance(doc, list):  # JSONL: every record self-identifies
        for i, rec in enumerate(doc):
            if not isinstance(rec, dict) or "metric" not in rec:
                probs.append("jsonl record %d lacks a 'metric' key" % i)
        return probs
    if name.startswith("FLIGHT_"):
        return _flight_problems(doc)
    if not isinstance(doc, dict):
        return ["top level is %s, expected object" % type(doc).__name__]
    if "cmd" in doc and "rc" in doc:
        return probs  # supervisor replay record — cmd+rc is the contract
    if "rows" in doc or "measurements" in doc:
        section = "rows" if "rows" in doc else "measurements"
        if not isinstance(doc.get("complete"), bool):
            probs.append("missing boolean 'complete' "
                         "(resumable-artifact contract)")
        if not any(k in doc for k in ("platform", "inner_platform")):
            probs.append("missing platform tag "
                         "('platform' or 'inner_platform')")
        rows = doc[section]
        if not isinstance(rows, list):
            probs.append("'%s' is not a list" % section)
        elif not all(isinstance(r, dict) for r in rows):
            probs.append("'%s' holds non-object entries" % section)
        if name == "BENCH_MESH.json":
            probs.extend(_mesh_problems(doc))
        if name == "BENCH_SPEC.json":
            probs.extend(_spec_problems(doc))
        if name == "BENCH_SPEC2.json":
            probs.extend(_spec2_problems(doc))
        if name == "BENCH_DISAGG.json":
            probs.extend(_disagg_problems(doc))
        if name == "BENCH_QCOMPUTE.json":
            probs.extend(_qcompute_problems(doc))
        if name == "BENCH_KVTIER.json":
            probs.extend(_kvtier_problems(doc))
        if name == "BENCH_ROUTER.json":
            probs.extend(_router_problems(doc))
        if name == "BENCH_DEADLINE.json":
            probs.extend(_deadline_problems(doc))
        if name == "PROFILE_MEM.json":
            probs.extend(_memprofile_problems(doc))
        return probs
    if "metric" not in doc:
        probs.append("no 'rows', no supervisor record, no 'metric' key "
                     "— unidentifiable artifact")
    return probs


def validate(path: str) -> list:
    """Problems for one file ([] = clean)."""
    base = os.path.basename(path)
    if base.startswith("TRACE_"):
        return _trace_problems(path)
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        return ["unreadable: %s" % e]
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        # JSON-LINES fallback: every non-blank
        # line must parse on its own
        recs = []
        for i, line in enumerate(text.splitlines()):
            if not line.strip():
                continue
            try:
                recs.append(json.loads(line))
            except json.JSONDecodeError as e:
                return ["neither JSON nor JSON-LINES (line %d: %s)"
                        % (i + 1, e)]
        if not recs:
            return ["empty file"]
        doc = recs
    return _problems(doc, os.path.basename(path))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv:
        paths = argv
        missing = [p for p in paths if not os.path.exists(p)]
        if missing:
            for p in missing:
                print("validate_artifact: %s: missing" % p)
            return 1
    else:
        paths = sorted(p for pat in PATTERNS
                       for p in glob.glob(os.path.join(REPO, pat)))
    bad = 0
    for p in paths:
        probs = validate(p)
        rel = os.path.relpath(p, REPO)
        if probs:
            bad += 1
            for msg in probs:
                print("validate_artifact: %s: %s" % (rel, msg))
        else:
            print("validate_artifact: %s: ok" % rel)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
