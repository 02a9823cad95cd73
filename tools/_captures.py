"""Append-only durable capture log for benchmark rows.

Every measured row from bench.py and tools/op_bench.py is appended to
``BENCH_CAPTURES.jsonl`` at the repo root — a COMMITTED artifact — so a
measurement leaves a durable, attributable record (the reference
persists its numbers next to the harness too:
operators/benchmark/op_tester.cc).
Each record carries a UTC timestamp and the git sha at measurement
time, so any number can be traced to the exact code that produced it.

Knobs:
  BENCH_CAPTURES_PATH  override the destination file (tests point this
                       at a tmp path)
  BENCH_NO_PERSIST=1   disable persistence entirely
"""
from __future__ import annotations

import json
import os
import subprocess
from datetime import datetime, timezone

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_sha_cache = None


def git_sha() -> str:
    """Short sha of HEAD, cached; 'unknown' outside a git checkout."""
    global _sha_cache
    if _sha_cache is None:
        try:
            out = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO,
                capture_output=True, text=True, timeout=10)
            _sha_cache = out.stdout.strip() or "unknown"
        except Exception:
            _sha_cache = "unknown"
    return _sha_cache


def captures_path() -> str:
    return os.environ.get(
        "BENCH_CAPTURES_PATH", os.path.join(_REPO, "BENCH_CAPTURES.jsonl"))


def persist_row(row: dict, kind: str = "bench") -> bool:
    """Append one measured row (with ts/git_sha/kind prepended).

    Never raises: a read-only checkout or full disk must not take down
    the bench whose primary contract is the stdout JSON row. Returns
    whether the write happened.
    """
    if os.environ.get("BENCH_NO_PERSIST") == "1":
        return False
    rec = {"ts": datetime.now(timezone.utc).isoformat(timespec="seconds"),
           "git_sha": git_sha(), "kind": kind}
    rec.update(row)
    try:
        with open(captures_path(), "a") as f:
            f.write(json.dumps(rec) + "\n")
        return True
    except Exception:
        # includes json TypeError on a non-serializable field: the
        # stdout row is the primary contract and must still be printed
        return False
