"""Repo guard checks, test-enforced (the reference runs these as hack/
scripts wired into pre-commit/CI: check-file-length.sh, check-log-pii.sh,
check-wiring-tests.sh, verify-rbac-sync.sh — here they are pytest cases
so the same gate runs with the suite, no shell harness needed)."""

from __future__ import annotations

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "omnia_tpu")

MAX_FILE_LINES = 800  # reference check-file-length discipline

# ---------------------------------------------------------------------------
# Knob-guard registry: EVERY EngineConfig field / MockEngine ctor knob maps
# to the knobs-off guard test proving its off value is a guarded true
# no-op ("<test_file>::<test_name>"), or to "structural: <why>" for
# shape/placement knobs with no off state. The static guards checker
# (omnia_tpu/analysis/guardcheck.py, tier-1 via tests/test_analysis.py)
# cross-checks this dict against the real knob lists and the named test
# functions — adding a knob without registering its guard fails the
# suite. Keep it a plain string-literal dict (it is parsed by AST).
# ---------------------------------------------------------------------------

KNOB_GUARDS = {
    "EngineConfig.num_slots": "structural: decode batch shape — no off state",
    "EngineConfig.max_seq": "structural: KV cache shape — no off state",
    "EngineConfig.prefill_buckets": "structural: compiled prefill shapes",
    "EngineConfig.dtype": "structural: compute dtype — no off state",
    "EngineConfig.dp": "structural: mesh axis; 1 builds no mesh (with tp*sp=1)",
    "EngineConfig.tp": "structural: mesh axis; 1 builds no mesh (with dp*sp=1)",
    "EngineConfig.sp": "test_guards.py::test_default_knobs_off_are_true_noop",
    "EngineConfig.long_prefill_threshold":
        "structural: ring-prefill cutover; dead while sp=1",
    "EngineConfig.decode_chunk": "structural: steps per dispatch — no off state",
    "EngineConfig.decode_chunk_variants":
        "structural: extra compiled chunk sizes; () adds none",
    "EngineConfig.decode_pipeline":
        "structural: in-flight chunk depth — no off state",
    "EngineConfig.max_sessions":
        "test_guards.py::test_default_knobs_off_are_true_noop",
    "EngineConfig.spec_decode":
        "test_guards.py::test_default_knobs_off_are_true_noop",
    "EngineConfig.spec_decode_max":
        "test_spec_decode.py::test_spec_knobs_off_are_true_noop",
    "EngineConfig.spec_gate_window":
        "test_spec_decode.py::test_spec_knobs_off_are_true_noop",
    "EngineConfig.quant":
        "test_guards.py::test_default_knobs_off_are_true_noop",
    "EngineConfig.kv_quant": "test_guards.py::test_kv_quant_none_is_true_noop",
    "EngineConfig.kv_pages":
        "test_guards.py::test_kv_pages_zero_is_true_noop",
    "EngineConfig.kv_page_tokens":
        "structural: page size / paged-kernel block; dead while kv_pages=0",
    "EngineConfig.prefix_cache_slots":
        "test_prefix_cache.py::test_disabled_pool_is_true_noop",
    "EngineConfig.prefix_cache_rows":
        "structural: pool-entry row cap; dead while prefix_cache_slots=0",
    "EngineConfig.prefix_cache_publish_threshold":
        "structural: publish heuristic; dead while prefix_cache_slots=0",
    "EngineConfig.prefix_cache_min_tokens":
        "structural: publish/seed floor; dead while prefix_cache_slots=0",
    "EngineConfig.prefix_cache_host_entries":
        "structural: host-tier cap; dead while prefix_cache_slots=0",
    "EngineConfig.grammar":
        "test_grammar.py::test_grammar_off_engine_allocates_no_grammar_state",
    "EngineConfig.max_queue":
        "test_guards.py::test_lifecycle_knobs_off_are_true_noop",
    "EngineConfig.watchdog_s":
        "test_guards.py::test_lifecycle_knobs_off_are_true_noop",
    "EngineConfig.grammar_max_states":
        "structural: device table capacity; dead while grammar=False",
    "EngineConfig.prefill_chunk_tokens":
        "test_guards.py::test_interleave_off_is_true_noop",
    "EngineConfig.flight_events":
        "test_flight.py::test_flight_off_is_true_noop",
    "EngineConfig.warmup_threads":
        "test_coldstart.py::test_warmup_threads_zero_is_true_noop",
    "MockEngine.kv_quant":
        "test_guards.py::test_mock_knobs_off_are_true_noop",
    "MockEngine.fault_plan":
        "test_guards.py::test_mock_knobs_off_are_true_noop",
    "MockEngine.max_queue":
        "test_guards.py::test_mock_knobs_off_are_true_noop",
    "MockEngine.watchdog_s":
        "test_guards.py::test_mock_knobs_off_are_true_noop",
    "MockEngine.prefill_chunk_tokens":
        "test_guards.py::test_mock_knobs_off_are_true_noop",
    "MockEngine.flight_events":
        "test_guards.py::test_mock_knobs_off_are_true_noop",
    "MockEngine.kv_pages":
        "test_guards.py::test_mock_knobs_off_are_true_noop",
    "MockEngine.kv_page_tokens":
        "structural: mirror page size; dead while kv_pages=0",
    "MockEngine.spec_decode":
        "test_guards.py::test_mock_knobs_off_are_true_noop",
    "MockEngine.spec_decode_max":
        "structural: mirror depth cap; dead while spec_decode=0",
    "MockEngine.spec_gate_window":
        "structural: mirror gate window; dead while spec_decode=0",
    "MockEngine.warmup_threads":
        "test_coldstart.py::test_mock_warmup_threads_zero_is_true_noop",
    "MockEngine.coldstart":
        "structural: injected progress tracker (ColdStartTracker); "
        "default-constructed when absent, never a behavior switch",
    "MockEngine.name":
        "structural: request-id prefix only (fleet-unique ids for the "
        "traffic simulator's flight-terminal join); never a behavior "
        "switch — default keeps the historical 'mock-N' ids",
    "MockEngine.role":
        "test_disagg.py::test_pooled_fleet_is_true_noop",
}


def _py_files():
    for dirpath, _dirs, files in os.walk(PKG):
        for fn in files:
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def test_file_length_guard():
    """No source file grows unreviewably large (check-file-length.sh)."""
    over = []
    for path in _py_files():
        with open(path) as f:
            n = sum(1 for _ in f)
        if n > MAX_FILE_LINES:
            over.append((os.path.relpath(path, REPO), n))
    assert not over, f"files over {MAX_FILE_LINES} lines: {over}"


def test_log_pii_guard():
    """Log statements must not interpolate user message content
    (check-log-pii.sh): `logger.*(...content...)` is how transcripts leak
    into aggregated logs."""
    pat = re.compile(
        r"logger\.(?:info|warning|error|debug|exception)\([^)]*"
        r"(?:\bmsg\.content\b|\.content\b|utterance|transcript)",
    )
    hits = []
    for path in _py_files():
        with open(path) as f:
            for i, line in enumerate(f, 1):
                if pat.search(line):
                    hits.append(f"{os.path.relpath(path, REPO)}:{i}")
    assert not hits, f"log statements carrying message content: {hits}"


def test_wiring_test_guard():
    """Every console-script entry point has a wiring test that names it
    (check-wiring-tests.sh: each binary's main wiring must be asserted).
    tomllib imports lazily: it is 3.11+ stdlib, and an import at module
    top would knock out the WHOLE guard module on older interpreters."""
    import pytest

    tomllib = pytest.importorskip("tomllib")
    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        scripts = tomllib.load(f)["project"]["scripts"]
    tests_blob = ""
    tdir = os.path.join(REPO, "tests")
    for fn in os.listdir(tdir):
        if fn.endswith(".py"):
            with open(os.path.join(tdir, fn)) as f:
                tests_blob += f.read()
    missing = []
    for target in scripts.values():
        fn_name = target.split(":")[1]
        if fn_name not in tests_blob:
            missing.append(fn_name)
    assert not missing, f"entry points with no wiring test: {missing}"


def test_rbac_sync_guard():
    """The installed ClusterRole must cover every CRD the generator ships
    (verify-rbac-sync.sh), and each CRD must have its committed YAML."""
    from omnia_tpu.operator.crds import GROUP, KINDS
    from omnia_tpu.operator.install import render_install

    out = render_install()
    role = next(m for m in out if m["kind"] == "ClusterRole")
    covered = any(
        GROUP in r["apiGroups"] and ("*" in r["resources"])
        for r in role["rules"]
    )
    per_resource = {
        res for r in role["rules"] if GROUP in r["apiGroups"]
        for res in r["resources"]
    }
    for kind, (plural, _fn, _s) in KINDS.items():
        assert covered or plural in per_resource, f"RBAC misses {plural}"
        assert os.path.exists(
            os.path.join(REPO, "deploy", "crds", f"{plural}.yaml")
        ), f"missing committed CRD yaml for {kind}"


def test_guard_walk_covers_grammar_subsystem():
    """The guard sweep must see omnia_tpu/engine/grammar/ — and the
    package must stay jax-free at the source level: importing it with
    grammar=off must allocate no device arrays (tests/test_grammar.py
    asserts the import-time half in a subprocess). The source-level
    half moved into the static analyzer's ``jaxfree`` rule
    (omnia_tpu/analysis/jaxfree.py — AST-based, so a function-local
    import no longer slips past the old line regex); this guard pins
    that the rule still COVERS the package and reports it clean."""
    rels = {os.path.relpath(p, PKG) for p in _py_files()}
    gdir = os.path.join("engine", "grammar")
    expected = {"__init__.py", "fsm.py", "regex.py", "jsonfsm.py", "cache.py"}
    present = {os.path.basename(r) for r in rels if r.startswith(gdir + os.sep)}
    assert expected <= present, f"guard walk misses {expected - present}"
    from omnia_tpu.analysis.core import analyze_file_set, walk_py
    from omnia_tpu.analysis.jaxfree import check_jaxfree, jaxfree_files

    files = jaxfree_files(walk_py(REPO, "omnia_tpu"))
    covered = {os.path.basename(f) for f in files
               if f.startswith("omnia_tpu/engine/grammar/")}
    assert expected <= covered, f"jaxfree rule misses {expected - covered}"
    findings = check_jaxfree(analyze_file_set(REPO, files))
    assert not findings, [f.render() for f in findings]


def test_guard_walk_covers_kube_subsystem():
    """The guard sweep (file-length, PII-log, no-silent-except) must see
    omnia_tpu/kube/ — a package added outside the walk would dodge every
    rule in this file."""
    rels = {os.path.relpath(p, PKG) for p in _py_files()}
    kube = {r for r in rels if r.startswith("kube" + os.sep)}
    for expected in ("client.py", "store.py", "apiserver.py", "watch.py",
                     "config.py", "leader.py"):
        assert os.path.join("kube", expected) in kube, (
            f"guard walk misses omnia_tpu/kube/{expected}"
        )


def test_install_objects_round_trip_apiserver_shim():
    """envtest-grade gate (VERDICT r5 weak #6): EVERY object render_install
    emits — with every optional bundle enabled — must be ACCEPTED by the
    apiserver shim's validation chain (structural lint for builtins,
    strict CRD OpenAPI for CRs, admission for the omnia group), and a
    broken object must be REJECTED. Rendered YAML that only ever passed
    a client-side lint is how dead manifests rot."""
    from omnia_tpu.kube.apiserver import ApiServerShim
    from omnia_tpu.kube.client import KubeClient
    from omnia_tpu.operator.install import render_install

    manifests = render_install({
        "encryption": {"enabled": True},
        "observability": {"enabled": True},
    })
    shim = ApiServerShim().start()
    try:
        client = KubeClient(shim.local_config())
        for m in manifests:
            # CRDs come early in the render order, so CR kinds register
            # before anything needs them — same ordering kubectl relies on.
            client.apply(m)  # raises ApiError/Unprocessable on rejection
        # and the schema gate has teeth: a typo'd CR bounces with 422.
        import pytest

        from omnia_tpu.kube.client import Unprocessable

        with pytest.raises(Unprocessable):
            client.create({
                "apiVersion": "omnia.tpu/v1alpha1", "kind": "Provider",
                "metadata": {"name": "bad", "namespace": "default"},
                "spec": {"type": "mock", "typoField": True},
            })
        with pytest.raises(Unprocessable):
            client.create({
                "apiVersion": "apps/v1", "kind": "Deployment",
                "metadata": {"name": "bad-deploy", "namespace": "default"},
                "spec": {"selector": {"matchLabels": {"a": "b"}},
                         "template": {"metadata": {"labels": {"a": "WRONG"}},
                                      "spec": {"containers": [
                                          {"name": "c", "image": "x"}]}}},
            })
    finally:
        shim.stop()


def test_kv_quant_none_is_true_noop():
    """EngineConfig.kv_quant=None must be a guarded no-op: caches stay
    plain arrays of the configured dtype (zero scale tensors allocated,
    pool included), and the compiled decode program's operand signature
    is byte-identical to a pre-kv_quant engine — one flat tensor per
    cache and no int8 anywhere in the lowered module."""
    import jax
    import jax.numpy as jnp

    from omnia_tpu.engine import EngineConfig, InferenceEngine
    from omnia_tpu.models import get_config
    from omnia_tpu.models.kv_quant import QuantKV

    eng = InferenceEngine(
        get_config("test-tiny"),
        EngineConfig(num_slots=2, max_seq=64, prefill_buckets=(16,),
                     dtype="float32", max_sessions=0, prefix_cache_slots=2),
    )
    for c in (eng._ck, eng._cv, eng._pk, eng._pv):
        assert not isinstance(c, QuantKV)
        assert c.dtype == jnp.float32
    leaves = jax.tree.leaves((eng._ck, eng._cv, eng._pk, eng._pv))
    assert len(leaves) == 4  # one tensor per cache — no scales beside them
    assert all(leaf.dtype != jnp.int8 for leaf in leaves)
    assert eng.metrics["kv_quant_enabled"] == 0
    lowered = eng._decode_fn_single.lower(
        eng.params, eng._ck, eng._cv, eng._tokens, eng._positions,
        eng._active, eng._budget, eng._stop_ids, eng._key_data, eng._temp,
        eng._top_p, eng._top_k,
    )
    text = lowered.as_text()
    assert "xi8>" not in text and "i8[" not in text, (
        "kv_quant=None traced int8 into the decode program"
    )
    # And the inverse sanity: int8 engines DO carry QuantKV caches.
    q8 = InferenceEngine(
        get_config("test-tiny"),
        EngineConfig(num_slots=2, max_seq=64, prefill_buckets=(16,),
                     dtype="float32", max_sessions=0, kv_quant="int8"),
    )
    assert isinstance(q8._ck, QuantKV) and q8._ck.q.dtype == jnp.int8


def test_kv_pages_zero_is_true_noop():
    """ISSUE 11 guard: kv_pages=0 must allocate ZERO page state — plain
    [L, B, S, H, D] caches (no PagedKV wrapper, no page table, no
    allocator, no paged programs), zero-valued pool gauges — and the
    compiled decode program must be byte-identical regardless of the
    (dead) kv_page_tokens knob. The paged engine, by contrast, carries
    the PagedKV operands and a live allocator."""
    from omnia_tpu.engine import EngineConfig, InferenceEngine, SamplingParams
    from omnia_tpu.models import get_config
    from omnia_tpu.models.paged_kv import PagedKV

    base = dict(num_slots=2, max_seq=64, prefill_buckets=(16,),
                dtype="float32", max_sessions=0)
    off = InferenceEngine(get_config("test-tiny"), EngineConfig(**base), seed=3)
    # kv_page_tokens is dead while kv_pages=0: ANY value (even one that
    # does not divide max_seq) must change nothing.
    off2 = InferenceEngine(
        get_config("test-tiny"), EngineConfig(**base, kv_page_tokens=7), seed=3
    )
    for eng in (off, off2):
        assert not isinstance(eng._ck, PagedKV)
        assert not isinstance(eng._cv, PagedKV)
        assert eng._pages is None and not eng._paged_on()
        assert eng._page_copy_fn is None
        assert eng._gather_pages_fn is None and eng._scatter_pages_fn is None
        for key in ("kv_pages_total", "kv_pages_free", "kv_page_cow_copies"):
            assert eng.metrics[key] == 0, (key, eng.metrics[key])
        assert eng.metrics["kv_page_fragmentation"] == 0.0

    def lowered(eng):
        return eng._decode_fn_single.lower(
            eng.params, eng._ck, eng._cv, eng._tokens, eng._positions,
            eng._active, eng._budget, eng._stop_ids, eng._key_data,
            eng._temp, eng._top_p, eng._top_k,
        ).as_text()

    assert lowered(off) == lowered(off2)

    # Identical greedy tokens off-vs-on (the equivalence battery in
    # tests/test_kv_pages.py covers the full matrix; this is the guard's
    # smoke half) and the paged engine's state is really paged.
    on = InferenceEngine(
        get_config("test-tiny"),
        EngineConfig(**base, kv_pages=10, kv_page_tokens=16), seed=3,
    )
    assert isinstance(on._ck, PagedKV) and on._pages is not None
    assert on.metrics["kv_pages_total"] == 9  # page 0 reserved for trash
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    t_off, _ = off.generate([4, 5, 6], sp)
    t_on, _ = on.generate([4, 5, 6], sp)
    assert t_off == t_on


def test_lifecycle_knobs_off_are_true_noop():
    """ISSUE 7 guard: deadline_s=None / max_queue=0 / watchdog_s=None
    must trace ZERO new operands and change ZERO behavior. The whole
    hardening layer is host-side by design, so even knobs-ON engines
    lower byte-identical decode programs; knobs-off engines must also
    take the exact pre-existing host paths (no watchdog threads, no
    deadline state, zero-valued counters) and emit identical tokens."""
    from omnia_tpu.engine import EngineConfig, InferenceEngine, SamplingParams
    from omnia_tpu.models import get_config

    base = dict(num_slots=2, max_seq=64, prefill_buckets=(8,),
                dtype="float32", max_sessions=0)
    off = InferenceEngine(get_config("test-tiny"), EngineConfig(**base), seed=3)
    on = InferenceEngine(
        get_config("test-tiny"),
        EngineConfig(**base, max_queue=4, watchdog_s=30.0), seed=3,
    )

    def lowered(eng):
        return eng._decode_fn_single.lower(
            eng.params, eng._ck, eng._cv, eng._tokens, eng._positions,
            eng._active, eng._budget, eng._stop_ids, eng._key_data,
            eng._temp, eng._top_p, eng._top_k,
        ).as_text()

    # Zero new operands: the compiled decode program is byte-identical
    # whether the lifecycle knobs are on or off.
    assert lowered(off) == lowered(on)

    # Zero behavior change: a deadline-less request on the knobs-off
    # engine carries no deadline state and produces the same greedy
    # tokens as the knobs-on engine (the knobs only ever bite when a
    # deadline/TTL/overload actually occurs).
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    h = off.submit([1, 2, 3], sp)
    with off._lock:
        assert off._waiting[0][0].deadline_at is None
    import threading as _threading

    t_off, _ = off.generate([4, 5, 6], sp)
    t_on, _ = on.generate([4, 5, 6], sp)
    assert t_off == t_on
    while off.step():
        pass
    h.collect_tokens(timeout=5)
    # watchdog_s=None syncs inline: no omnia-chunk-sync thread ever ran
    # (and none CAN anymore — the watchdog path now shares the ONE
    # long-lived omnia-chunk-drainer per engine, engine/devloop.py).
    assert not [
        t for t in _threading.enumerate() if t.name == "omnia-chunk-sync"
    ]
    # The knobs-off engine builds no devloop state at all; the knobs-on
    # engine's watchdog runs through its single long-lived drainer, not
    # per-chunk thread churn (one ChunkDrainer, reused across chunks).
    assert off._devloop is None
    d = on._devloop._drainer  # built on the first chunk read, never before
    assert d is not None and not d.poisoned and d._thread.is_alive()
    on.stop()
    assert not d._thread.is_alive()
    # The always-present counters exist and stayed zero on both engines.
    for eng in (off, on):
        for key in ("requests_shed", "deadline_exceeded", "watchdog_trips"):
            assert eng.metrics[key] == 0, (key, eng.metrics[key])


def test_interleave_off_is_true_noop():
    """ISSUE 8 guard: prefill_chunk_tokens=0 must build ZERO mixed
    programs, never hold an in-flight interleaved prefill, and keep the
    compiled decode family byte-identical to a knobs-on engine (the
    feature only ADDS programs — the decode step body is shared, so the
    lowered decode programs cannot differ either way) while emitting
    identical greedy tokens through the monolithic paths."""
    from omnia_tpu.engine import EngineConfig, InferenceEngine, SamplingParams
    from omnia_tpu.models import get_config

    base = dict(num_slots=2, max_seq=64, prefill_buckets=(8,),
                dtype="float32", max_sessions=0)
    off = InferenceEngine(get_config("test-tiny"), EngineConfig(**base), seed=3)
    on = InferenceEngine(
        get_config("test-tiny"),
        EngineConfig(**base, prefill_chunk_tokens=4), seed=3,
    )
    # Knob off: no mixed programs exist, no interleave state ever forms.
    assert off._mixed_fns == {} and off._mixed_sample_fns == {}
    assert off.cfg.mixed_prefill_buckets() == ()
    assert off._prefilling is None
    # Knob on: the family exists per piece bucket (incl. the 1-token
    # cache-end degrade bucket).
    assert set(on._mixed_fns) == set(on.cfg.mixed_prefill_buckets()) != set()
    assert set(on._mixed_sample_fns) == set(on._mixed_fns)

    def lowered(eng):
        return eng._decode_fn_single.lower(
            eng.params, eng._ck, eng._cv, eng._tokens, eng._positions,
            eng._active, eng._budget, eng._stop_ids, eng._key_data,
            eng._temp, eng._top_p, eng._top_k,
        ).as_text()

    # The decode programs are byte-identical knob-on vs knob-off: the
    # shared step body refactor changed nothing about their lowering.
    assert lowered(off) == lowered(on)

    # Identical greedy tokens (a solo request takes the monolithic path
    # on both engines — interleaving only engages with live decode).
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    t_off, _ = off.generate([4, 5, 6], sp)
    t_on, _ = on.generate([4, 5, 6], sp)
    assert t_off == t_on
    # The always-present counters exist and stayed zero on the off
    # engine (no stall possible: nothing was decoding).
    for key in ("mixed_steps", "interleaved_prefill_tokens",
                "decode_stall_steps"):
        assert off.metrics[key] == 0, (key, off.metrics[key])


def test_default_knobs_off_are_true_noop():
    """ISSUE 9 guard-conformance stragglers: quant=None / spec_decode=0 /
    max_sessions=0 / sp=1 had no registered knobs-off guard. One tiny
    engine at those defaults must build ZERO feature state: no quantized
    param leaves, no verify program or spec counters, no session
    registry activity even when a session_id is supplied, and no ring
    prefill program."""
    import jax
    import jax.numpy as jnp

    from omnia_tpu.engine import EngineConfig, InferenceEngine, SamplingParams
    from omnia_tpu.models import get_config
    from omnia_tpu.models import quant as wquant

    eng = InferenceEngine(
        get_config("test-tiny"),
        EngineConfig(num_slots=2, max_seq=64, prefill_buckets=(8,),
                     dtype="float32", max_sessions=0),
        seed=5,
    )
    # quant=None: full-precision params, no int8 leaves anywhere.
    assert not wquant.params_quantized(eng.params)
    assert all(
        leaf.dtype != jnp.int8 for leaf in jax.tree.leaves(eng.params)
    )
    # spec_decode=0: no verify program, the spec path never engages —
    # _spec_step is a config check that dispatches nothing.
    assert eng._verify_fn is None and eng._verify_decode_fn is None
    assert not eng._spec_step()
    assert eng._spec_gate is None
    # sp=1: no ring-prefill program.
    assert eng._prefill_ring_fn is None
    # max_sessions=0: a session_id is accepted but creates NO session
    # state — sessionless serving exactly.
    h = eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=4),
                   session_id="ignored")
    while eng.step():
        pass
    toks, fin = h.collect_tokens(timeout=30)
    assert fin.finish_reason is not None and toks
    assert eng._sessions == {}
    for key in ("spec_steps", "spec_proposed", "spec_accepted",
                "spec_gate_state", "spec_index_bytes",
                "session_offloads", "session_restores"):
        assert eng.metrics[key] == 0, (key, eng.metrics[key])
    assert eng.metrics["spec_accept_ema"] == 0.0


def test_mock_knobs_off_are_true_noop():
    """MockEngine's lifecycle/parity knobs at their defaults must leave
    playback byte-identical to the pre-knob mock: no shed/deadline/
    watchdog/mixed-step counts, the always-idle queue signal, and zero
    kv-quant round-trip activity."""
    from omnia_tpu.engine.mock import MockEngine, Scenario
    from omnia_tpu.engine.types import SamplingParams

    m = MockEngine([Scenario("hi", "hello-world")])
    assert m.queue_depth() == 0  # max_queue=0 keeps the idle signal
    # flight_events=0: zero recorder state, no span plumbing engaged.
    assert m._flight is None and m.tracer is None
    toks, fin = m.generate(
        m.tokenizer.encode("hi"), SamplingParams(max_tokens=32)
    )
    assert m.tokenizer.decode(toks) == "hello-world"
    assert fin.finish_reason.value == "stop"
    for key in ("requests_shed", "deadline_exceeded", "watchdog_trips",
                "mixed_steps", "interleaved_prefill_tokens",
                "kv_quant_enabled", "kv_quant_rows_written",
                "flight_enabled", "kv_pages_total", "kv_pages_free",
                "kv_page_cow_copies", "spec_steps", "spec_proposed",
                "spec_accepted", "spec_gate_state", "spec_index_bytes"):
        assert m.metrics[key] == 0, (key, m.metrics[key])
    assert m.metrics["kv_quant_roundtrip_rel_err"] == 0.0
    assert m.metrics["spec_accept_ema"] == 0.0
    assert m.metrics["kv_page_fragmentation"] == 0.0
    # kv_pages=0: no mirror allocator exists at all.
    assert m._page_alloc is None and m._page_slots == []
    # spec_decode=0: no gate controller, no index ever built.
    assert m._spec_gate is None


def test_knob_guard_registry_is_conformant():
    """The registry above is only worth anything if it stays in sync
    with the real knob lists — delegate the cross-check to the static
    guards rule (the same code tier-1 test_analysis runs)."""
    from omnia_tpu.analysis.cli import run_checkers

    findings = [f for f in run_checkers(REPO, ("guards",)) if not f.waived]
    assert not findings, [f.render() for f in findings]


def test_no_silent_broad_except():
    """Broad handlers (`except Exception:`/bare `except:`) followed by a
    bare `pass` with no comment swallow faults silently — they must log
    or annotate why. Narrow typed handlers are self-documenting and
    exempt."""
    offenders = []
    for path in _py_files():
        with open(path) as f:
            lines = f.readlines()
        for i, line in enumerate(lines):
            if re.search(r"except(?:\s+(?:Exception|BaseException))?\s*:\s*$", line):
                nxt = lines[i + 1] if i + 1 < len(lines) else ""
                if nxt.strip() == "pass" and "#" not in line and "#" not in nxt:
                    offenders.append(f"{os.path.relpath(path, REPO)}:{i + 1}")
    assert not offenders, f"silent broad excepts (log or annotate): {offenders}"
