"""Incremental, resumable measurement artifacts — the shared protocol.

Every measurement tool in this package (lm_perf, convergence_bench)
follows one contract, for runs under a time limit:

- the artifact is rewritten ATOMICALLY after every row, so a sweep
  killed at its limit keeps everything it measured;
- ``complete`` stays false until the final flush, so a rerun resumes a
  stage until its sweep truly finished;
- on restart, rows are reused only when the caller's ``match``
  predicate accepts them (platform + full configuration + iteration
  count — a CPU debug row must never publish as a TPU number).

This module is that contract's single implementation.
"""
from __future__ import annotations

import json
import logging
import os
from typing import Callable

log = logging.getLogger("bigdl_tpu.artifacts")


def write_artifact(path: str, result: dict) -> None:
    """Atomic JSON rewrite (no-op when path is falsy): a kill mid-write
    must never leave truncated JSON that zeroes out resume progress."""
    if not path:
        return
    from bigdl_tpu.utils import fs
    fs.atomic_write(path, (json.dumps(result, indent=2) + "\n").encode())


def load_artifact(path: str):
    """The prior artifact document, or None.  A MISSING file resumes
    nothing silently (cold start); an EXISTING-but-unparseable one
    (truncated by a kill mid-flush on a non-atomic writer, disk
    corruption) is treated as absent with a loud warning — the sweep
    restarts instead of crashing the round on a json decode error.
    Parse ONCE per run: callers indexing several sections must not
    re-read a file a concurrent runner may be rewriting between
    reads."""
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError) as e:
            log.warning(
                "artifact %s exists but is unreadable (%s: %s) — "
                "treating it as absent and restarting the sweep",
                path, type(e).__name__, e)
    return None


def index_rows(doc, *, match: Callable[[dict, dict], bool],
               key: Callable[[dict], object],
               section: str = "rows") -> dict:
    """Reusable rows of one section, keyed by ``key(row)``.
    ``match(document, row)`` decides reuse — it sees the whole document
    so platform/config headers can gate every row."""
    prev: dict = {}
    if isinstance(doc, dict):
        for r in doc.get(section, []):
            if match(doc, r):
                prev[key(r)] = r
    return prev


def load_resumable_rows(path: str, *, match: Callable[[dict, dict], bool],
                        key: Callable[[dict], object],
                        section: str = "rows") -> dict:
    """One-shot convenience: load_artifact + index_rows."""
    return index_rows(load_artifact(path), match=match, key=key,
                      section=section)
