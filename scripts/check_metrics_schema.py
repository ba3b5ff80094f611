#!/usr/bin/env python
"""Validate telemetry JSONL, trace-plane files, manifests and the other
schema-tagged records against the documented schemas
(fluxmpi_tpu/telemetry/schema.py — the single source of truth).

Usage:
    python scripts/check_metrics_schema.py [FILE ...]

- ``*.jsonl`` files: every line must be a valid telemetry flush record
  (schema "fluxmpi_tpu.telemetry/v1") — except lines carrying
  ``"schema": "fluxmpi_tpu.request/v1"`` (the serving plane's
  per-request terminal records, ``init(request_log=...)`` /
  ``FLUXMPI_TPU_REQUEST_LOG``), which validate as request records,
  and lines carrying ``"schema": "fluxmpi_tpu.fleet/v1"`` (the
  :class:`FleetCollector`'s per-interval snapshot bank,
  ``init(fleet=...)`` / ``FLUXMPI_TPU_FLEET``), which validate as
  fleet snapshots, and lines carrying
  ``"schema": "fluxmpi_tpu.autotune/v1"`` (layout-autotuner records),
  which validate as autotune records, and lines carrying
  ``"schema": "fluxmpi_tpu.resize/v1"`` (the live-resize badput bank,
  ``init(resize=...)`` / ``FLUXMPI_TPU_RESIZE``), which validate as
  resize records (a number for every ``RESIZE_PHASES`` phase, totals
  that sum; transient handoff half-records pass untouched). Metric
  names in the framework-owned ``fault.`` / ``checkpoint.`` / ``goodput.`` /
  ``anomaly.`` / ``compile.`` / ``memory.`` namespaces must come from
  ``schema.KNOWN_METRIC_NAMES``
  (``fault.injected``, ``checkpoint.retries``, the run-health plane's
  ``goodput.bucket_seconds``/``goodput.mfu``/``anomaly.triggered``
  family; ``train.resumes`` and the ``train.preemption`` /
  ``anomaly.<rule>`` trace instants are validated the same way) —
  producer drift there fails the check.
- ``*.json`` files carrying ``"schema": "fluxmpi_tpu.trace/v1"``:
  dispatched on ``kind`` — a trace export (``Tracer.export`` /
  ``scripts/merge_traces.py`` output), a flight-recorder dump, or a
  watchdog hang dump. Anomaly diagnostics bundles
  (``fluxmpi_anomaly.<process>.json``, written by the
  :class:`AnomalyDetector` on trigger) and OOM forensics bundles
  (``fluxmpi_oom.<process>.json``, written by ``train_loop`` when an
  XLA ``RESOURCE_EXHAUSTED`` escapes the dispatch loop — live-array
  census + per-device HBM stats + peak watermark) are
  watchdog-dump-kind records with an extra ``anomaly`` / ``oom``
  section and validate through the same path. The device plane's
  ``compile.`` / ``memory.`` metric namespaces are closed like the
  run-health ones — unknown names there fail the check.
- ``*.json`` files carrying ``"schema": "fluxmpi_tpu.manifest/v1"``
  (the ``<step>.manifest.json`` topology sidecar every checkpoint save
  writes): validated against the manifest schema — leaf
  shapes/dtypes/partition specs, mesh axes, loader geometry.
- ``*.json`` files carrying ``"schema": "fluxmpi_tpu.autotune/v1"``
  (the ``FLUXMPI_TPU_AUTOTUNE_BANK`` file or a ``<ckpt>.autotune.json``
  sidecar): validated as layout-autotuner records — candidate table
  consistency (pruned ⇒ no trial, trials count, winner trialed).
- ``*.json`` files carrying ``"schema": "fluxmpi_tpu.resize/v1"``: a
  completed live-resize record saved whole validates like a bank line;
  a pending handoff stamp (``.fluxmpi_resize.json``, ``"handoff":
  true``) passes untouched.
- any other ``*.json`` file carries no schema tag this script knows and
  is an error: a record that cannot be told apart cannot be held to a
  schema.

The schema module is loaded by file path, NOT via ``import fluxmpi_tpu``:
this script must stay runnable in a second without booting jax or any
backend.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_schema():
    # One loader for "the schema module, by file path, without booting
    # jax": fluxmpi_tpu/analysis/context.py owns it (fluxlint checks
    # metric-name and env-var drift against the same source), and this
    # script borrows it instead of keeping a second copy.
    path = os.path.join(_REPO, "fluxmpi_tpu", "analysis", "context.py")
    spec = importlib.util.spec_from_file_location(
        "_fluxmpi_analysis_context", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.load_schema_module(_REPO)


def check_file(path: str, schema) -> list[str]:
    """Validate one file; returns error strings prefixed with location."""
    errors: list[str] = []
    with open(path, "r", encoding="utf-8") as f:
        content = f.read()
    if path.endswith(".jsonl"):
        for i, line in enumerate(content.splitlines(), 1):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as exc:
                errors.append(f"{path}:{i}: not JSON: {exc}")
                continue
            if (
                isinstance(rec, dict)
                and rec.get("schema") == schema.REQUEST_SCHEMA
            ):
                # Per-request terminal record (the serving plane's
                # request log) — a different line schema sharing the
                # JSONL transport.
                for e in schema.validate_request_record(rec):
                    errors.append(f"{path}:{i}: {e}")
                continue
            if (
                isinstance(rec, dict)
                and rec.get("schema") == schema.FLEET_SCHEMA
            ):
                # Fleet snapshot line (the cross-host collector's bank,
                # replayed by scripts/fleet_report.py).
                for e in schema.validate_fleet_snapshot(rec):
                    errors.append(f"{path}:{i}: {e}")
                continue
            if (
                isinstance(rec, dict)
                and rec.get("schema") == schema.AUTOTUNE_SCHEMA
            ):
                # Layout-autotuner record appended to a JSONL stream
                # (e.g. a bank of tunes) — the same shape as the
                # FLUXMPI_TPU_AUTOTUNE_BANK file.
                for e in schema.validate_autotune_record(rec):
                    errors.append(f"{path}:{i}: {e}")
                continue
            if (
                isinstance(rec, dict)
                and rec.get("schema") == schema.RESIZE_SCHEMA
            ):
                # Live-resize event record (the FLUXMPI_TPU_RESIZE
                # bank). Handoff stamps share the schema tag but are
                # half-records by design (the resumed world completes
                # and removes them) — skipped, not failed.
                if not rec.get("handoff"):
                    for e in schema.validate_resize_record(rec):
                        errors.append(f"{path}:{i}: {e}")
                continue
            for e in schema.validate_record(rec):
                errors.append(f"{path}:{i}: {e}")
        return errors
    try:
        data = json.loads(content)
    except json.JSONDecodeError as exc:
        return [f"{path}: not JSON: {exc}"]
    if isinstance(data, dict) and data.get("schema") == schema.TRACE_SCHEMA:
        # Trace-plane file (span export / flight recorder / watchdog
        # dump): validate_trace_file dispatches on its 'kind'.
        return [f"{path}: {e}" for e in schema.validate_trace_file(data)]
    if isinstance(data, dict) and data.get("schema") == schema.MANIFEST_SCHEMA:
        # Checkpoint topology manifest (the elastic-restore sidecar).
        return [f"{path}: {e}" for e in schema.validate_manifest(data)]
    if isinstance(data, dict) and data.get("schema") == schema.FLEET_SCHEMA:
        # A single fleet snapshot saved as .json (FleetCollector
        # .snapshot() dumped whole rather than banked line-by-line).
        return [f"{path}: {e}" for e in schema.validate_fleet_snapshot(data)]
    if isinstance(data, dict) and data.get("schema") == schema.AUTOTUNE_SCHEMA:
        # A layout-autotuner bank file (FLUXMPI_TPU_AUTOTUNE_BANK) or a
        # <ckpt>.autotune.json sidecar: the banked winner + candidate
        # table a later init(parallel="auto") trusts instead of
        # re-running trials.
        return [
            f"{path}: {e}" for e in schema.validate_autotune_record(data)
        ]
    if isinstance(data, dict) and data.get("schema") == schema.RESIZE_SCHEMA:
        # A completed resize record saved whole; pending handoff stamps
        # (.fluxmpi_resize.json, "handoff": true) are transient
        # half-records and pass untouched.
        if data.get("handoff"):
            return errors
        return [f"{path}: {e}" for e in schema.validate_resize_record(data)]
    tag = data.get("schema") if isinstance(data, dict) else None
    return [f"{path}: no known 'schema' tag (got {tag!r})"]


def main(paths: list[str]) -> int:
    schema = _load_schema()
    if not paths:
        print("check_metrics_schema: nothing to validate", file=sys.stderr)
        return 0
    errors: list[str] = []
    for path in paths:
        if not os.path.exists(path):
            errors.append(f"{path}: no such file")
            continue
        errors.extend(check_file(path, schema))
    for e in errors:
        print(e, file=sys.stderr)
    print(
        f"check_metrics_schema: {len(paths)} file(s), "
        f"{len(errors)} error(s)"
    )
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
