"""Trace-purity checker (rule ``purity``).

A function traced by ``jax.jit`` / ``lax.scan`` / ``shard_map`` /
``pl.pallas_call`` runs ONCE at trace time; host side effects inside it
silently happen never (or once, at compile) instead of per step — the
classic "time.time() in a scan body" bug. This checker finds the traced
bodies in a module and flags host effects inside them:

- ``print(...)`` / ``open(...)``
- ``time.*`` (host clock inside a traced body)
- bare ``random.*`` and ``np.random.*`` (host RNG; ``jax.random`` is
  the device-side API and is fine)
- ``.item()`` and ``np.asarray(...)`` (implicit device→host syncs)
- ``.block_until_ready()``
- ``global`` / ``nonlocal`` declarations and ``self.<attr>`` writes
  (Python-state mutation from a traced body)

Traced-body discovery is module-local and transitive: a function is
traced if it is decorated with / passed to a tracer entry point, if it
is defined inside a traced function, or if a traced function calls it
by name. Cross-module calls are not followed — each listed module is
checked against its own tracer call sites.
"""

from __future__ import annotations

import ast

from omnia_tpu.analysis.core import Finding, SourceFile

#: Files whose traced bodies are checked (the compiled-program surface —
#: plus the flight-recorder layer and the scheduler/placement seams it
#: instruments: all flight timing must be captured strictly host-side,
#: so a host clock slipping into a traced body there is exactly this
#: rule's bug class).
PURITY_FILES_PREFIXES: tuple[str, ...] = (
    "omnia_tpu/engine/programs.py",
    "omnia_tpu/engine/interleave.py",
    "omnia_tpu/engine/spec_decode.py",
    "omnia_tpu/engine/flight.py",
    "omnia_tpu/engine/scheduler.py",
    "omnia_tpu/engine/placement.py",
    "omnia_tpu/engine/paged.py",
    "omnia_tpu/engine/warmup.py",
    "omnia_tpu/ops/",
    "omnia_tpu/models/",
    "omnia_tpu/parallel/",
    # The traffic simulator is host-side by contract; listing it makes
    # any future traced body inside it subject to the same rule.
    "omnia_tpu/evals/trafficsim/",
    # The fleet scaler is host-side by contract (scale decisions are
    # stats arithmetic); a traced body here would be the same bug class.
    "omnia_tpu/engine/fleet.py",
    # Role routing and the handoff plane are stats arithmetic + worker
    # RPCs; a traced body here would be the same bug class.
    "omnia_tpu/engine/disagg.py",
    # The watchdog's chunk drainer is host-side by contract (a thread
    # and a queue); a traced body here would be the same bug class.
    "omnia_tpu/engine/devloop.py",
    # The engine-loop phase spans are host annotations on the profiler's
    # clock; one opened inside a traced body would run once, at trace
    # time, and name nothing.
    "omnia_tpu/engine/phases.py",
    "omnia_tpu/engine/lifecycle.py",
)

#: Call heads that trace their function argument(s).
_TRACER_ATTRS = frozenset({"jit", "scan", "shard_map", "pallas_call",
                           "while_loop", "fori_loop", "cond", "vmap",
                           "checkpoint", "remat", "grad", "value_and_grad"})

_HOST_MODULES = frozenset({"time", "random"})
_NP_ALIASES = frozenset({"np", "numpy"})


def purity_files(all_files: list[str]) -> list[str]:
    return [
        f for f in all_files
        if any(
            f == p or (p.endswith("/") and f.startswith(p))
            for p in PURITY_FILES_PREFIXES
        )
    ]


def _call_attr_name(func: ast.expr) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


class _TracedIndex:
    """All function defs in a module + which are (transitively) traced.
    ``traced`` holds FunctionDef/AsyncFunctionDef AND Lambda nodes —
    a lambda handed to a tracer entry point is a traced body too."""

    def __init__(self, tree: ast.AST):
        self.defs: dict[str, list[ast.FunctionDef]] = {}
        self.parents: dict[ast.FunctionDef, ast.FunctionDef | None] = {}
        self.traced: set[ast.AST] = set()
        self._collect(tree, None)
        self._seed(tree)
        self._closure()

    def _collect(self, node: ast.AST, parent) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if not isinstance(node, ast.ClassDef):
                    # Methods are reachable only via attribute access,
                    # never a bare Name — indexing them by name would
                    # falsely trace any method sharing a name with a
                    # jitted function. (Decorator seeding still covers
                    # @jit methods directly, by node.)
                    self.defs.setdefault(child.name, []).append(child)
                self.parents[child] = parent
                self._collect(child, child)
            else:
                self._collect(child, parent)

    def _seed_arg(self, arg: ast.expr) -> None:
        """Mark one tracer argument: a named def, an in-place lambda, or
        either wrapped in ``functools.partial(...)`` (the idiom both
        ``@partial(jax.jit, ...)`` bodies and
        ``pallas_call(partial(kernel, ...))`` kernels use)."""
        if isinstance(arg, ast.Name) and arg.id in self.defs:
            self.traced.update(self.defs[arg.id])
        elif isinstance(arg, ast.Lambda):
            self.traced.add(arg)  # traced in place
        elif isinstance(arg, ast.Call) and _call_attr_name(arg.func) == "partial":
            for sub in list(arg.args) + [kw.value for kw in arg.keywords]:
                self._seed_arg(sub)

    @staticmethod
    def _decorator_traces(deco: ast.expr) -> bool:
        """True when a decorator traces the function it decorates:
        ``@jax.jit``, ``@jit(...)``, or ``@functools.partial(jax.jit,
        ...)`` (the partial head itself is not a tracer — its FIRST
        argument is)."""
        if isinstance(deco, ast.Call):
            head = _call_attr_name(deco.func)
            if head in _TRACER_ATTRS:
                return True
            if head == "partial" and deco.args:
                return _call_attr_name(deco.args[0]) in _TRACER_ATTRS
            return False
        return _call_attr_name(deco) in _TRACER_ATTRS

    def _seed(self, tree: ast.AST) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                head = _call_attr_name(node.func)
                if head not in _TRACER_ATTRS:
                    continue
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    self._seed_arg(arg)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(self._decorator_traces(d) for d in node.decorator_list):
                    self.traced.add(node)

    def _closure(self) -> None:
        # (a) defs nested inside a traced def are traced; (b) module-
        # local functions CALLED from a traced body are traced. Iterate
        # to fixpoint (the sets are tiny).
        changed = True
        while changed:
            changed = False
            for fns in self.defs.values():
                for fn in fns:
                    if fn in self.traced:
                        continue
                    parent = self.parents.get(fn)
                    if parent is not None and parent in self.traced:
                        self.traced.add(fn)
                        changed = True
            for fn in list(self.traced):
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call) and isinstance(
                        node.func, ast.Name
                    ):
                        for callee in self.defs.get(node.func.id, ()):
                            if callee not in self.traced:
                                self.traced.add(callee)
                                changed = True


def _iter_body(fn: ast.AST, traced: set[ast.AST]):
    """Walk one traced body WITHOUT descending into nested nodes that
    are traced roots themselves — every nested def of a traced function
    (closure rule) and every directly-seeded lambda is walked as its own
    root, so each violation is attributed to exactly one body."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if node in traced:
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _check_body(src: SourceFile, fn: ast.AST, traced: set[ast.AST],
                findings: list[Finding]) -> None:
    where = f"traced body {getattr(fn, 'name', '<lambda>')!r}"
    for node in _iter_body(fn, traced):
        if isinstance(node, (ast.Global, ast.Nonlocal)):
            findings.append(Finding(
                "purity", src.rel, node.lineno,
                f"{where} declares {'global' if isinstance(node, ast.Global) else 'nonlocal'}"
                f" — Python-state mutation inside a traced body",
            ))
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                if (
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                ):
                    findings.append(Finding(
                        "purity", src.rel, node.lineno,
                        f"{where} writes self.{t.attr} — object mutation "
                        f"inside a traced body happens at TRACE time, "
                        f"not per step",
                    ))
        elif isinstance(node, ast.Call):
            _check_call(src, where, node, findings)
        elif isinstance(node, ast.Attribute):
            if isinstance(node.value, ast.Name) and node.value.id in _HOST_MODULES:
                findings.append(Finding(
                    "purity", src.rel, node.lineno,
                    f"{where} uses {node.value.id}.{node.attr} — host "
                    f"{'clock' if node.value.id == 'time' else 'RNG'} "
                    f"inside a traced body runs once at trace time",
                ))
            elif (
                node.attr == "random"
                and isinstance(node.value, ast.Name)
                and node.value.id in _NP_ALIASES
            ):
                findings.append(Finding(
                    "purity", src.rel, node.lineno,
                    f"{where} uses {node.value.id}.random — host RNG "
                    f"inside a traced body",
                ))


def _check_call(src: SourceFile, where: str, node: ast.Call,
                findings: list[Finding]) -> None:
    func = node.func
    if isinstance(func, ast.Name) and func.id in ("print", "open"):
        findings.append(Finding(
            "purity", src.rel, node.lineno,
            f"{where} calls {func.id}() — host side effect inside a "
            f"traced body",
        ))
        return
    if not isinstance(func, ast.Attribute):
        return
    if func.attr == "item":
        findings.append(Finding(
            "purity", src.rel, node.lineno,
            f"{where} calls .item() — implicit device→host sync inside "
            f"a traced body",
        ))
    elif func.attr == "block_until_ready":
        findings.append(Finding(
            "purity", src.rel, node.lineno,
            f"{where} calls .block_until_ready() inside a traced body",
        ))
    elif func.attr == "asarray" and isinstance(func.value, ast.Name) and (
        func.value.id in _NP_ALIASES
    ):
        findings.append(Finding(
            "purity", src.rel, node.lineno,
            f"{where} calls {func.value.id}.asarray() — implicit "
            f"device→host sync inside a traced body (use jnp.asarray)",
        ))


def check_purity(sources: dict[str, SourceFile]) -> list[Finding]:
    findings: list[Finding] = []
    in_scope = set(purity_files(sorted(sources)))
    for src in sources.values():
        # Self-scoped (like jaxfree/locks): a full run shares one source
        # map across rules, so files loaded for OTHER rules must not
        # widen this one — `--rule purity` and the full suite agree.
        if src.rel not in in_scope or src.tree is None:
            continue
        index = _TracedIndex(src.tree)
        for fn in index.traced:
            _check_body(src, fn, index.traced, findings)
    return findings
