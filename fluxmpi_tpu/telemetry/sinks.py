"""Pluggable flush targets for :class:`~fluxmpi_tpu.telemetry.MetricsRegistry`.

A sink receives the full flush record (schema.py shape) and owns its
transport. Three are provided: a JSONL file writer (one line per
flush), an in-memory list for tests, and a rank-0 console reporter.
``NullSink`` exists so overhead can be measured with emission wired up
but going nowhere.
"""

from __future__ import annotations

import json
import sys
from typing import Any, IO

__all__ = [
    "Sink",
    "JSONLSink",
    "MemorySink",
    "ConsoleSink",
    "NullSink",
]


class Sink:
    """Interface: ``write(record)`` per flush, ``close()`` at shutdown."""

    def write(self, record: dict[str, Any]) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class NullSink(Sink):
    """Discards every record (overhead measurement / disabled emission)."""

    def write(self, record: dict[str, Any]) -> None:
        pass


class MemorySink(Sink):
    """Keeps records in a list — test and notebook introspection."""

    def __init__(self) -> None:
        self.records: list[dict[str, Any]] = []

    def write(self, record: dict[str, Any]) -> None:
        self.records.append(record)

    def clear(self) -> None:
        self.records.clear()


class JSONLSink(Sink):
    """Append one JSON line per flush to a file.

    The file is opened lazily on first write (constructing the sink on a
    rank that never flushes creates nothing) and every line is flushed
    through to the OS — a killed run keeps all completed lines, which is
    the whole point of a crash-forensics stream. Every controller process
    should write to its own path in multi-host runs (pass e.g.
    ``f"metrics.{jax.process_index()}.jsonl"``); lines carry ``process``
    so merged streams stay attributable.
    """

    def __init__(self, path: str):
        self.path = path
        self._file: IO[str] | None = None

    def write(self, record: dict[str, Any]) -> None:
        line = json.dumps(record) + "\n"
        if self._file is None:
            self._file = open(self.path, "a", encoding="utf-8")
        self._file.write(line)
        self._file.flush()

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None


class ConsoleSink(Sink):
    """Compact per-flush summary on stdout, lead process only.

    Multi-host etiquette: every process records, only process 0 prints —
    the serialized all-rank printer (:func:`fluxmpi_tpu.fluxmpi_println`)
    takes a global barrier per line, far too heavy for periodic metrics.
    """

    def __init__(self, stream: IO[str] | None = None, max_metrics: int = 8):
        self._stream = stream
        self.max_metrics = max_metrics

    def _is_lead(self) -> bool:
        try:
            from ..runtime import is_initialized

            if is_initialized():
                import jax

                return jax.process_index() == 0
        except Exception:
            pass
        return True

    def write(self, record: dict[str, Any]) -> None:
        if not self._is_lead():
            return
        parts = []
        for m in record.get("metrics", [])[: self.max_metrics]:
            label = ",".join(f"{k}={v}" for k, v in m.get("labels", {}).items())
            name = m["name"] + (f"{{{label}}}" if label else "")
            if m["type"] == "histogram":
                if m.get("count"):
                    parts.append(
                        f"{name} n={m['count']} mean={m['mean']:.4g} "
                        f"max={m['max']:.4g}"
                    )
            else:
                parts.append(f"{name}={m['value']:.6g}")
        n_more = len(record.get("metrics", [])) - self.max_metrics
        if n_more > 0:
            parts.append(f"(+{n_more} more)")
        print("telemetry: " + "  ".join(parts), file=self._stream or sys.stdout)
