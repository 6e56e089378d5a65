"""Validate an exported telemetry JSONL file against the checked-in
event schema.

  python tools/check_obs_schema.py EVENTS.jsonl [more.jsonl ...] \
      [--schema tools/obs_schema.json] [--require engine_iter,serve_batch]

Deliberately repo-import-free: CI validates the uploaded artifact with
nothing but the stdlib and ``tools/obs_schema.json`` (the checked-in
serialization of ``repro.obs.schema.EVENT_SCHEMA``; a unit test asserts
the two never diverge).  The validation rules mirror
``repro.obs.schema.validate_event``:

  * every record needs a known ``"event"`` type and a numeric ``"ts"``;
  * every field the schema marks required must be present with the
    declared type (``float`` accepts ints; ``bool`` is rejected where an
    int/float is asked — bool is an int subclass in Python), and every
    field it marks optional has that type where present;
  * extra fields are always allowed (events are forward-extensible).

``--require`` additionally fails the run when the file contains no
record of a listed event type — the CI smoke uses it to prove the
workload actually exercised the engine and serving instrumentation, not
just produced a syntactically valid (possibly empty) file.

Exit status: 0 clean, 1 any violation (reported with line numbers).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
DEFAULT_SCHEMA = REPO_ROOT / "tools" / "obs_schema.json"

TYPE_TAGS = {
    "str": (str,),
    "int": (int,),
    "float": (int, float),
    "bool": (bool,),
}


def validate_record(rec, schema):
    """Violation strings for one parsed record (empty when valid)."""
    errs = []
    ev = rec.get("event")
    if not isinstance(ev, str):
        return ["missing/invalid 'event' field"]
    spec = schema["events"].get(ev)
    if spec is None:
        return [f"unknown event type {ev!r}"]
    if not isinstance(rec.get("ts"), (int, float)) \
            or isinstance(rec.get("ts"), bool):
        errs.append(f"{ev}: missing/invalid 'ts'")
    optional = spec.get("optional", {})
    for field, tag in [*spec["required"].items(), *optional.items()]:
        if field not in rec:
            if field not in optional:
                errs.append(f"{ev}: missing required field {field!r}")
            continue
        v = rec[field]
        if isinstance(v, bool) and tag in ("int", "float"):
            errs.append(f"{ev}: field {field!r} expected {tag}, got bool")
        elif not isinstance(v, TYPE_TAGS[tag]):
            errs.append(f"{ev}: field {field!r} expected {tag}, "
                        f"got {type(v).__name__}")
    return errs


def check_file(path: Path, schema, seen: dict) -> list:
    errs = []
    with open(path, encoding="utf-8") as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                errs.append(f"{path}:{lineno}: not JSON ({e})")
                continue
            if not isinstance(rec, dict):
                errs.append(f"{path}:{lineno}: record is not an object")
                continue
            for v in validate_record(rec, schema):
                errs.append(f"{path}:{lineno}: {v}")
            ev = rec.get("event")
            if isinstance(ev, str):
                seen[ev] = seen.get(ev, 0) + 1
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("files", nargs="+", help="JSONL event files")
    ap.add_argument("--schema", default=str(DEFAULT_SCHEMA))
    ap.add_argument("--require", default=None,
                    help="comma-separated event types that must appear "
                         "at least once across the input files")
    args = ap.parse_args(argv)

    schema = json.loads(Path(args.schema).read_text())
    seen: dict = {}
    errs = []
    total = 0
    for fname in args.files:
        p = Path(fname)
        if not p.exists():
            errs.append(f"{p}: no such file")
            continue
        before = sum(seen.values())
        errs.extend(check_file(p, schema, seen))
        total += sum(seen.values()) - before
    if args.require:
        for ev in args.require.split(","):
            ev = ev.strip()
            if ev and not seen.get(ev):
                errs.append(f"required event type {ev!r} never appeared")
    if errs:
        for e in errs:
            print(e, file=sys.stderr)
        print(f"FAIL: {len(errs)} violation(s) over {total} record(s)",
              file=sys.stderr)
        return 1
    counts = ", ".join(f"{k}={v}" for k, v in sorted(seen.items()))
    print(f"OK: {total} record(s) valid ({counts})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
