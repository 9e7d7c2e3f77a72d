"""Serialization of recorded traces as a JSONL event log.

One JSON object per line; spans (``type: "span"``), events (``type:
"event"``), and an optional trailing metrics snapshot (``type:
"metrics"``). Round-trips losslessly through ``read_jsonl`` →
``Recorder``-shaped ``TraceData``. The times are the recorder's clock;
the same spans sit on the device's clock in a ``jax.profiler`` trace
(``repro.obs.trace``), which is where they line up with device work.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from repro.obs.trace import Recorder, Span


@dataclass
class TraceData:
    """A deserialized trace: what ``read_jsonl`` hands back."""
    spans: List[Span] = field(default_factory=list)
    events: List[Dict[str, Any]] = field(default_factory=list)
    metrics: Optional[Dict[str, Any]] = None
    meta: Dict[str, Any] = field(default_factory=dict)

    def find(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def children_of(self, span: Span) -> List[Span]:
        return [s for s in self.spans if s.parent_id == span.span_id]


def trace_lines(rec: Recorder, *, metrics: Optional[Dict[str, Any]] = None,
                meta: Optional[Dict[str, Any]] = None) -> List[str]:
    """The JSONL lines for a recorder's contents (spans in completion
    order, then events, then optional metrics/meta records)."""
    lines: List[str] = []
    if meta:
        lines.append(json.dumps({"type": "meta", **meta}, sort_keys=True))
    for sp in rec.spans:
        lines.append(json.dumps(sp.to_dict(), sort_keys=True))
    for ev in rec.events:
        lines.append(json.dumps(ev, sort_keys=True))
    if metrics is not None:
        lines.append(json.dumps({"type": "metrics", "metrics": metrics},
                                sort_keys=True))
    return lines


def write_jsonl(path, rec: Recorder, *,
                metrics: Optional[Dict[str, Any]] = None,
                meta: Optional[Dict[str, Any]] = None) -> None:
    with open(path, "w") as fh:
        for line in trace_lines(rec, metrics=metrics, meta=meta):
            fh.write(line + "\n")


def read_jsonl(path) -> TraceData:
    data = TraceData()
    with open(path) as fh:
        for raw in fh:
            raw = raw.strip()
            if not raw:
                continue
            rec = json.loads(raw)
            kind = rec.get("type")
            if kind == "span":
                data.spans.append(Span.from_dict(rec))
            elif kind == "event":
                data.events.append(rec)
            elif kind == "metrics":
                data.metrics = rec.get("metrics")
            elif kind == "meta":
                data.meta = {k: v for k, v in rec.items() if k != "type"}
    return data
