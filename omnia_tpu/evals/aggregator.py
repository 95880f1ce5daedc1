"""Result aggregation + threshold gating.

Reference ee/pkg/arena/{aggregator,threshold}: per scenario×provider
cell — pass rate, error rate, latency percentiles, cost — then the job
threshold decides pass/fail for the whole run."""

from __future__ import annotations

import dataclasses
from typing import Optional

from omnia_tpu.evals.defs import Threshold, WorkResult


def percentile(values: list, p: float, empty=0.0):
    """Nearest-rank percentile over raw samples — THE evals-plane
    percentile definition (aggregator cells and the traffic simulator's
    report share it, so p95 columns on one gating surface agree).
    ``empty`` is returned for an empty sample set (0.0 here, None in
    the simulator report where absence must be visible)."""
    if not values:
        return empty
    s = sorted(values)
    idx = min(len(s) - 1, max(0, int(round(p / 100.0 * (len(s) - 1)))))
    return s[idx]


_percentile = percentile


@dataclasses.dataclass
class CellStats:
    scenario: str
    provider: str
    runs: int = 0
    passed: int = 0
    errors: int = 0
    latencies: list = dataclasses.field(default_factory=list)
    turn_latencies_ms: list = dataclasses.field(default_factory=list)
    cost_usd: float = 0.0
    tokens: int = 0
    # Traffic-simulator SLO view (evals/trafficsim): per-class offered/
    # met/error counters plus engine-stage TTFT / inter-token percentile
    # blocks sourced from flight-recorder LatencyBreakdowns ({"p50",
    # "p95", "p99", "count"}). Kept SEPARATE from runs/passed/errors —
    # the check-based plane's books — so the classic pass-rate gates
    # never judge simulator cells (and vice versa). Folding a second
    # report into the same cell sums the counters exactly and merges
    # the percentile blocks element-wise MAX (conservative for gating:
    # a p95 threshold then judges the worst window observed, never an
    # average that hides it).
    slo_offered: int = 0
    slo_met: int = 0
    slo_errors: int = 0
    ttft_ms: dict = dataclasses.field(default_factory=dict)
    itl_ms: dict = dataclasses.field(default_factory=dict)

    @property
    def pass_rate(self) -> float:
        return self.passed / self.runs if self.runs else 0.0

    @property
    def error_rate(self) -> float:
        return self.errors / self.runs if self.runs else 0.0

    @property
    def slo_attainment(self) -> float:
        return self.slo_met / self.slo_offered if self.slo_offered else 0.0

    def merge_percentiles(self, field: str, block: dict) -> None:
        mine = getattr(self, field)
        for k, v in block.items():
            if v is None:
                continue
            if k == "count":
                mine[k] = mine.get(k, 0) + v
            else:
                mine[k] = v if mine.get(k) is None else max(mine.get(k, v), v)

    def to_dict(self) -> dict:
        return {
            "scenario": self.scenario,
            "provider": self.provider,
            "runs": self.runs,
            "passed": self.passed,
            "errors": self.errors,
            "pass_rate": self.pass_rate,
            "error_rate": self.error_rate,
            "p50_latency_s": _percentile(self.latencies, 50),
            "p95_latency_s": _percentile(self.latencies, 95),
            # Per-turn percentiles (fleet SLO view — scenario latency
            # hides slow turns inside multi-turn scenarios).
            "p50_turn_ms": _percentile(self.turn_latencies_ms, 50),
            "p95_turn_ms": _percentile(self.turn_latencies_ms, 95),
            # Simulator SLO rows, beside the per-turn view (None until
            # a trafficsim report was folded in).
            "slo_attainment": (
                round(self.slo_attainment, 4) if self.slo_offered else None
            ),
            "slo_error_rate": (
                round(self.slo_errors / self.slo_offered, 4)
                if self.slo_offered else None
            ),
            "ttft_p50_ms": self.ttft_ms.get("p50"),
            "ttft_p95_ms": self.ttft_ms.get("p95"),
            "ttft_p99_ms": self.ttft_ms.get("p99"),
            "itl_p95_ms": self.itl_ms.get("p95"),
            "cost_usd": self.cost_usd,
            "tokens": self.tokens,
        }


class Aggregator:
    def __init__(self) -> None:
        self._cells: dict[tuple, CellStats] = {}
        self._seen: set[str] = set()

    def add(self, r: WorkResult) -> bool:
        """Fold one result; returns False for a duplicate work_id (the
        queue is at-least-once — a worker that crashed between publish
        and ack, or a reclaimed slow item, delivers twice)."""
        if r.work_id:
            if r.work_id in self._seen:
                return False
            self._seen.add(r.work_id)
        key = (r.scenario, r.provider)
        cell = self._cells.get(key)
        if cell is None:
            cell = self._cells[key] = CellStats(r.scenario, r.provider)
        cell.runs += 1
        if r.error:
            cell.errors += 1
        elif r.passed:
            cell.passed += 1
        cell.latencies.append(r.latency_s)
        cell.turn_latencies_ms.extend(r.turn_latency_ms)
        cell.cost_usd += r.cost_usd
        cell.tokens += r.tokens
        return True

    def add_slo_cells(self, report: dict,
                      provider: str = "trafficsim") -> int:
        """Fold a traffic-simulator report's per-scenario-class SLO
        cells (evals/trafficsim report schema) into CellStats rows:
        attainment counters, the exact error count, and the
        flight-recorder TTFT/ITL percentile blocks land beside the
        existing per-turn view — so one ArenaJob verdict can gate on
        both. Deliberately does NOT touch runs/passed: those belong to
        the check-based plane, and mapping offered→runs would let the
        default ``min_pass_rate=1.0`` gate fire on a class that is
        meeting its own attainment target (the SLO gates below are the
        simulator cells' verdict surface). Returns the number of
        classes folded; duplex classes the run skipped fold nothing."""
        folded = 0
        for name, cell in sorted(report.get("classes", {}).items()):
            slo = cell.get("slo")
            if slo is None:
                continue
            key = (name, provider)
            cs = self._cells.get(key)
            if cs is None:
                cs = self._cells[key] = CellStats(name, provider)
            cs.slo_offered += int(cell.get("offered", 0))
            cs.slo_met += int(slo.get("met_requests", 0))
            cs.slo_errors += int(slo.get("errors", 0))
            cs.tokens += int(cell.get("tokens_streamed", 0))
            cs.merge_percentiles("ttft_ms", cell.get("ttft_engine_ms", {}))
            cs.merge_percentiles("itl_ms", cell.get("itl_engine_ms", {}))
            folded += 1
        return folded

    def cells(self) -> list[CellStats]:
        return [self._cells[k] for k in sorted(self._cells)]

    def evaluate(self, threshold: Threshold) -> dict:
        """Job verdict: every cell must clear the threshold. Failure
        messages name the cell (scenario class) and the exact bound —
        percentile included — that broke."""
        failures = []
        for cell in self.cells():
            # Classic check-based gates judge only cells with check
            # runs: a cell holding nothing but folded simulator data
            # has runs == 0 and is judged by the SLO gates below.
            if cell.runs and cell.pass_rate < threshold.min_pass_rate:
                failures.append(
                    f"{cell.scenario}/{cell.provider}: pass_rate "
                    f"{cell.pass_rate:.2f} < {threshold.min_pass_rate:.2f}"
                )
            if cell.runs and cell.error_rate > threshold.max_error_rate:
                failures.append(
                    f"{cell.scenario}/{cell.provider}: error_rate "
                    f"{cell.error_rate:.2f} > {threshold.max_error_rate:.2f}"
                )
            if threshold.max_p95_latency_s is not None:
                p95 = _percentile(cell.latencies, 95)
                if p95 > threshold.max_p95_latency_s:
                    failures.append(
                        f"{cell.scenario}/{cell.provider}: p95 {p95:.2f}s "
                        f"> {threshold.max_p95_latency_s:.2f}s"
                    )
            # Simulator SLO gates: only engage on cells a trafficsim
            # report was folded into (slo_offered > 0 / blocks present),
            # so classic check-based jobs are unaffected.
            if (threshold.min_slo_attainment is not None
                    and cell.slo_offered > 0
                    and cell.slo_attainment < threshold.min_slo_attainment):
                failures.append(
                    f"{cell.scenario}/{cell.provider}: SLO attainment "
                    f"{cell.slo_attainment:.3f} < "
                    f"{threshold.min_slo_attainment:.3f}"
                )
            if threshold.max_p95_ttft_ms is not None:
                t95 = cell.ttft_ms.get("p95")
                if t95 is not None and t95 > threshold.max_p95_ttft_ms:
                    failures.append(
                        f"{cell.scenario}/{cell.provider}: TTFT p95 "
                        f"{t95:.1f}ms > {threshold.max_p95_ttft_ms:.1f}ms"
                    )
            if threshold.max_p95_itl_ms is not None:
                i95 = cell.itl_ms.get("p95")
                if i95 is not None and i95 > threshold.max_p95_itl_ms:
                    failures.append(
                        f"{cell.scenario}/{cell.provider}: inter-token p95 "
                        f"{i95:.1f}ms > {threshold.max_p95_itl_ms:.1f}ms"
                    )
        return {
            "passed": not failures,
            "failures": failures,
            "cells": [c.to_dict() for c in self.cells()],
        }
