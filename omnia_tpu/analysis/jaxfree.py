"""Jax-free-by-contract package checker (rule ``jaxfree``).

Some packages are jax-free BY CONTRACT: ``engine/grammar`` must be
importable with grammar=off allocating zero device arrays, which is
only provable if nothing in the package can ever touch jax (PR 3;
``tests/test_grammar.py`` asserts the import-time half in a
subprocess). This rule is the source-level half, absorbed from
``tests/test_guards.py``: no ``import jax`` / ``from jax ...`` at ANY
position (module top, function body, conditional) in a contracted
package. AST-based, so an import hidden inside a function no longer
slips past the old line-regex.
"""

from __future__ import annotations

import ast

from omnia_tpu.analysis.core import Finding, SourceFile

#: Repo-relative path prefixes (packages or single modules) that must
#: never import jax.
JAX_FREE_PACKAGES: tuple[str, ...] = (
    "omnia_tpu/engine/grammar/",
    "omnia_tpu/analysis/",
    # Cold-start tracker + warmup manifest: jax-free by contract so the
    # mock parity layer and the CI poisoned-jax subset can run it.
    "omnia_tpu/engine/coldstart.py",
    # Traffic simulator: the generator/report path and the mock-fleet
    # CLI must run in jax-less containers (the duplex driver's runtime
    # import is lazy and degrades to a recorded skip).
    "omnia_tpu/evals/trafficsim/",
    # Fleet scaler: queue-depth → replica-count decisions are host-side
    # arithmetic by contract — the operator's pod path runs it in
    # jax-less controller processes, and the CI poisoned-jax subset
    # proves the whole control loop without a device stack.
    "omnia_tpu/engine/fleet.py",
    # Role policy + handoff orchestration are host-side by contract:
    # the DisaggRouter must run in jax-less controller processes and
    # the CI poisoned-jax subset proves the routing/handoff plane
    # without a device stack.
    "omnia_tpu/engine/disagg.py",
    # The pipeline entry and the watchdog's chunk drainer are host-side
    # by contract — the CI poisoned-jax subset proves the drain plane
    # without a device stack (the readback's numpy import is lazy for
    # the same reason).
    "omnia_tpu/engine/devloop.py",
)


def jaxfree_files(all_files: list[str]) -> list[str]:
    return [
        f for f in all_files
        if any(f.startswith(p) for p in JAX_FREE_PACKAGES)
    ]


def check_jaxfree(sources: dict[str, SourceFile]) -> list[Finding]:
    findings: list[Finding] = []
    for src in sources.values():
        if not any(src.rel.startswith(p) for p in JAX_FREE_PACKAGES):
            continue
        if src.tree is None:
            continue
        for node in ast.walk(src.tree):
            bad = None
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "jax" or alias.name.startswith("jax."):
                        bad = alias.name
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if node.level == 0 and (mod == "jax" or mod.startswith("jax.")):
                    bad = mod
            if bad is not None:
                findings.append(Finding(
                    "jaxfree", src.rel, node.lineno,
                    f"imports {bad!r} inside a jax-free-by-contract "
                    f"package — the package must stay importable with "
                    f"zero device-array allocation",
                ))
    return findings
