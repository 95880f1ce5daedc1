"""Eval definitions: checks, scenarios, arena job specs, thresholds.

Mirrors the reference's eval model (reference ee/pkg/arena — ArenaJob
partitions a scenario × provider matrix into work items; ee/pkg/evals —
eval defs run as checks over turns, LLM-judge or assertion-based).
Checks are data, not code, so packs/CRDs can declare them:

  {"kind": "contains", "value": "refund"}
  {"kind": "regex", "value": "\\d+ days"}
  {"kind": "not_contains", "value": "I cannot"}
  {"kind": "max_latency_s", "value": 2.0}
  {"kind": "judge", "rubric": "Answers the question politely", "min_score": 0.7}
"""

from __future__ import annotations

import dataclasses
import re
import uuid
from typing import Optional


@dataclasses.dataclass
class Check:
    kind: str
    value: object = None
    rubric: str = ""
    min_score: float = 0.7
    name: str = ""

    @classmethod
    def from_dict(cls, d: dict) -> "Check":
        return cls(
            kind=d["kind"],
            value=d.get("value"),
            rubric=d.get("rubric", ""),
            min_score=float(d.get("min_score", 0.7)),
            name=d.get("name", d["kind"]),
        )

    def evaluate_sync(self, reply: str, latency_s: float) -> Optional[bool]:
        """Assertion checks evaluate locally; judge checks return None
        (the worker sends those to the Judge)."""
        if self.kind == "contains":
            return str(self.value).lower() in reply.lower()
        if self.kind == "not_contains":
            return str(self.value).lower() not in reply.lower()
        if self.kind == "regex":
            return re.search(str(self.value), reply) is not None
        if self.kind == "max_latency_s":
            return latency_s <= float(self.value)
        if self.kind == "judge":
            return None
        raise ValueError(f"unknown check kind {self.kind!r}")


@dataclasses.dataclass
class ScenarioTurn:
    user: str
    checks: list = dataclasses.field(default_factory=list)  # [Check]


@dataclasses.dataclass
class EvalScenario:
    name: str
    turns: list = dataclasses.field(default_factory=list)  # [ScenarioTurn]

    @classmethod
    def from_dict(cls, d: dict) -> "EvalScenario":
        return cls(
            name=d["name"],
            turns=[
                ScenarioTurn(
                    user=t["user"],
                    checks=[Check.from_dict(c) for c in t.get("checks", [])],
                )
                for t in d.get("turns", [])
            ],
        )


@dataclasses.dataclass
class Threshold:
    """Pass/fail gate over aggregated results (reference
    ee/pkg/arena/threshold). The three SLO bounds only engage on cells
    a traffic-simulator report was folded into
    (Aggregator.add_slo_cells) — classic check-based jobs never see
    them fire."""

    min_pass_rate: float = 1.0
    max_error_rate: float = 0.0
    max_p95_latency_s: Optional[float] = None
    # Simulator SLO gates (evals/trafficsim): per-class attainment and
    # flight-recorder-sourced engine percentile bounds.
    min_slo_attainment: Optional[float] = None
    max_p95_ttft_ms: Optional[float] = None
    max_p95_itl_ms: Optional[float] = None


@dataclasses.dataclass
class ArenaJobSpec:
    name: str
    scenarios: list  # [EvalScenario]
    providers: list  # [str] provider names (the matrix axis)
    repeats: int = 1
    mode: str = "direct"  # direct | fleet
    threshold: Threshold = dataclasses.field(default_factory=Threshold)

    @classmethod
    def from_dict(cls, d: dict) -> "ArenaJobSpec":
        th = d.get("threshold", {})
        return cls(
            name=d["name"],
            scenarios=[EvalScenario.from_dict(s) for s in d.get("scenarios", [])],
            providers=list(d.get("providers", [])),
            repeats=int(d.get("repeats", 1)),
            mode=d.get("mode", "direct"),
            threshold=Threshold(
                min_pass_rate=float(th.get("min_pass_rate", 1.0)),
                max_error_rate=float(th.get("max_error_rate", 0.0)),
                max_p95_latency_s=th.get("max_p95_latency_s"),
                min_slo_attainment=th.get("min_slo_attainment"),
                max_p95_ttft_ms=th.get("max_p95_ttft_ms"),
                max_p95_itl_ms=th.get("max_p95_itl_ms"),
            ),
        )


@dataclasses.dataclass
class WorkItem:
    """One unit of arena work: a scenario run against one provider."""

    job: str
    scenario: dict  # EvalScenario as dict (queue entries are JSON)
    provider: str
    repeat: int = 0
    mode: str = "direct"
    id: str = dataclasses.field(default_factory=lambda: uuid.uuid4().hex)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "WorkItem":
        return cls(**{k: d[k] for k in ("job", "scenario", "provider", "repeat", "mode", "id") if k in d})


@dataclasses.dataclass
class CheckResult:
    name: str
    passed: bool
    score: Optional[float] = None
    detail: str = ""


@dataclasses.dataclass
class WorkResult:
    work_id: str
    job: str
    scenario: str
    provider: str
    repeat: int
    checks: list = dataclasses.field(default_factory=list)  # [CheckResult]
    error: str = ""
    latency_s: float = 0.0
    tokens: int = 0
    cost_usd: float = 0.0
    worker: str = ""
    # Per-turn latencies (reference vu_pool.go WorkResult carries turn
    # timings for the fleet SLO story): raw ms samples + a fixed-bucket
    # histogram dict (vu_pool.LatencyHistogram.to_dict()).
    turn_latency_ms: list = dataclasses.field(default_factory=list)
    latency_hist: dict = dataclasses.field(default_factory=dict)

    @property
    def passed(self) -> bool:
        return not self.error and all(c.passed for c in self.checks)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "WorkResult":
        d = dict(d)
        d["checks"] = [CheckResult(**c) for c in d.get("checks", [])]
        return cls(**{k: d[k] for k in (
            "work_id", "job", "scenario", "provider", "repeat", "checks",
            "error", "latency_s", "tokens", "cost_usd", "worker",
            "turn_latency_ms", "latency_hist") if k in d})
