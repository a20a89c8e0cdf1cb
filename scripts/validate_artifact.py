#!/usr/bin/env python
"""Schema lint for the measurement artifacts the repo still produces.

Two families, told apart by file name:

  * ``FLIGHT_*`` incident bundles (``bigdl_tpu.obs.flight``) must carry
    every correlated section (spans, timeseries, state, diagnose_tpu,
    ...) and ``complete``;
  * ``TRACE_*`` files must satisfy the Chrome trace-event contract
    (delegated to scripts/validate_trace.py).

A file of any other family is reported as unknown, not passed: a
truncated or key-drifted artifact fails SILENTLY downstream (resume
identity never matching, `complete` read as falsy), and so does one
nobody checks.

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

#: the families' file-name prefixes
FAMILIES = ("FLIGHT_", "TRACE_")

#: where the repo keeps them
PATTERNS = tuple(f + "*.json" for f in FAMILIES) + (
    os.path.join("flight", "FLIGHT_*.json"),)

#: FlightRecorder bundle contract (bigdl_tpu.obs.flight._dump): every
#: key must be present — a partial bundle means the dump died mid-write
#: and the forensic evidence cannot be trusted
FLIGHT_KEYS = ("flight", "ts_unix", "ts", "detail", "spans",
               "active_requests", "timeseries", "state", "registry",
               "diagnose_tpu", "complete")


def _flight_problems(doc) -> list:
    """FLIGHT_*.json: the incident bundle is correlated evidence (spans
    + time-series window + diagnostics captured at one instant)."""
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


def validate(path: str) -> list:
    """Problems for one file ([] = clean)."""
    base = os.path.basename(path)
    if not base.startswith(FAMILIES):
        return ["unknown artifact family (known: %s)"
                % ", ".join(f + "*" for f in FAMILIES)]
    if base.startswith("TRACE_"):
        return _trace_problems(path)
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        return ["unreadable: %s" % e]
    except json.JSONDecodeError as e:
        return ["not JSON: %s" % e]
    return _flight_problems(doc)


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
