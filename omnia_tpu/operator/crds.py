"""CustomResourceDefinition manifests for the 9 declarative kinds.

The cluster-facing twin of `operator/resources.py` (reference
config/crd/bases/*.yaml, generated there by controller-gen from
api/v1alpha1 types). Here the CRDs are generated from the same enum
vocabularies the in-process admission validation uses
(`operator/validation.py`), so the schema the cluster enforces and the
schema the operator enforces cannot drift apart. `deploy/crds/*.yaml` is
the committed output; tests assert the files match this generator
(the controller-gen make-manifests discipline).

Structural-schema rules honored: every object schema either types its
properties or carries x-kubernetes-preserve-unknown-fields for
deliberately open maps (pack params, tool args, annotations).
"""

from __future__ import annotations

from omnia_tpu.operator.resources import (
    AGENT_MODES,
    API_VERSION,
    FACADE_TYPES,
    PROVIDER_ROLES,
    PROVIDER_TYPES,
    TOOL_HANDLER_TYPES,
)

GROUP = API_VERSION.split("/")[0]
VERSION = API_VERSION.split("/")[1]


def _str(enum=None, **kw):
    s = {"type": "string", **kw}
    if enum:
        s["enum"] = list(enum)
    return s


def _obj(props=None, required=None, open_=False, desc=None):
    s: dict = {"type": "object"}
    if props:
        s["properties"] = props
    if required:
        s["required"] = list(required)
    if open_:
        s["x-kubernetes-preserve-unknown-fields"] = True
    if desc:
        s["description"] = desc
    return s


def _arr(items):
    return {"type": "array", "items": items}


_INT = {"type": "integer"}
_NUM = {"type": "number"}
_BOOL = {"type": "boolean"}
_REF = _obj({"name": _str()}, required=["name"])


def _agent_runtime_schema() -> dict:
    facade = _obj(
        {
            "type": _str(enum=FACADE_TYPES),
            "path": _str(),
            "auth": _obj(open_=True),
        },
        required=["type"],
    )
    autoscaling = _obj({
        "minReplicas": _INT,
        "maxReplicas": _INT,
        "scaleToZero": _BOOL,
        "queueDepthTarget": _INT,
    })
    rollout = _obj({
        "steps": _arr(_obj({"weight": _INT, "pause_s": _NUM})),
        "analysis": _obj(open_=True),
        "autoPromote": _BOOL,
    })
    return _obj(
        {
            "mode": _str(enum=AGENT_MODES),
            "promptPackRef": _REF,
            "toolRegistryRef": _REF,
            "providers": _arr(_obj({
                "name": _str(),
                "providerRef": _REF,
                "role": _str(enum=PROVIDER_ROLES),
            }, required=["providerRef"])),
            "facades": _arr(facade),
            "context": _obj({"ttl_s": _NUM, "store": _str()}),
            "memoryRef": _REF,
            "privacyPolicyRef": _REF,
            "replicas": _INT,
            "autoscaling": autoscaling,
            "rollout": rollout,
            "duplex": _obj({"enabled": _BOOL, "format": _obj(open_=True)}),
            "evals": _arr(_obj(open_=True)),
            "externalAuth": _obj(open_=True),
            "serviceGroup": _str(),
            "facadeImage": _str(),
            "runtimeImage": _str(),
            "tpuChips": _INT,
            # Multi-host engine: pods per model replica (StatefulSet +
            # jax.distributed; parallel/distributed.py env contract).
            "tpuHosts": _INT,
            "podOverrides": _obj(open_=True),
        },
        required=["promptPackRef", "providers"],
    )


def _provider_schema() -> dict:
    return _obj(
        {
            "type": _str(enum=PROVIDER_TYPES),
            "role": _str(enum=PROVIDER_ROLES),
            "model": _str(),
            "options": _obj(open_=True),
            # Key names match the admission/controller vocabulary
            # (validation.py pricing checks, controller._resolve_refs) —
            # the apiserver-shim schema gate caught the earlier *MTokUSD
            # drift.
            "pricing": _obj({
                "inputPerMTok": _NUM,
                "outputPerMTok": _NUM,
            }),
            "engine": _obj({
                "numSlots": _INT,
                "maxSeq": _INT,
                "dtype": _str(),
                "dp": _INT,
                "tp": _INT,
                "decodeChunk": _INT,
                "maxSessions": _INT,
                # Cross-session shared-prefix KV pool (docs/serving.md).
                "prefixCacheSlots": _INT,
                "prefixCacheRows": _INT,
            }),
        },
        required=["type"],
    )


def _prompt_pack_schema() -> dict:
    return _obj(
        {
            "content": _obj(open_=True, desc="compiled pack JSON"),
            "sourceRef": _REF,
            "version": _str(),
        },
        required=["content"],
    )


def _tool_registry_schema() -> dict:
    # handler carries per-type config blocks, mirroring the reference's
    # HandlerEntry (reference internal/runtime/tools/config.go:131-169:
    # grpcConfig/mcpConfig/openAPIConfig alongside the plain http fields).
    handler = _obj({
        "type": _str(enum=TOOL_HANDLER_TYPES),
        "url": _str(),
        "method": _str(),
        "headers": _obj(open_=True),
        "timeoutSeconds": _NUM,
        "endpoint": _str(),
        "remoteName": _str(),
        "operation": _str(),
        "spec": _obj(open_=True),
        "specURL": _str(),
        "baseURL": _str(),
        "grpcConfig": _obj({
            "endpoint": _str(),
            "tls": _BOOL,
            "authToken": _str(),
        }, open_=True),
        "mcpConfig": _obj({
            "transport": _str(enum=("stdio", "http", "streamable-http")),
            "command": _str(),
            "args": _arr(_str()),
            "env": _obj(open_=True),
            "workDir": _str(),
            "endpoint": _str(),
            "headers": _obj(open_=True),
            "toolFilter": _obj({
                "allowlist": _arr(_str()),
                "blocklist": _arr(_str()),
            }),
        }),
        "openAPIConfig": _obj({
            "specURL": _str(),
            "baseURL": _str(),
            "headers": _obj(open_=True),
        }, open_=True),
    }, required=["type"])
    return _obj({
        # Reachability probing (reference toolregistry_types.go
        # ProbeConfig): the controller TCP-dials each network handler and
        # surfaces per-tool Available/Unavailable + a registry phase.
        "probe": _obj({
            "enabled": _BOOL,
            "timeoutSeconds": _NUM,
            "intervalSeconds": _NUM,
        }),
        "tools": _arr(_obj({
            "name": _str(),
            "description": _str(),
            "handler": handler,
            "inputSchema": _obj(open_=True),
            "input_schema": _obj(open_=True),  # legacy spelling, examples/
            "auth": _obj(open_=True),
            "timeout_s": _NUM,
        }, required=["name"])),
    }, required=["tools"])


def _workspace_schema() -> dict:
    return _obj({
        "environment": _str(),
        "services": _arr(_obj({
            "name": _str(),
            "sessionApi": _BOOL,
            "memoryApi": _BOOL,
        }, required=["name"])),
        "roleBindings": _arr(_obj(open_=True)),
        "storage": _obj(open_=True),
    }, required=["environment"])


def _agent_policy_schema() -> dict:
    return _obj({
        "rules": _arr(_obj({
            "tools": _arr(_str()),
            "effect": _str(enum=("allow", "deny")),
            "when": _str(),
        }, required=["effect"])),
    }, required=["rules"])


def _memory_policy_schema() -> dict:
    return _obj({
        "tiers": _arr(_str()),
        "ttl_s": _NUM,
        "halfLife_s": _NUM,
        "consentCategories": _arr(_str()),
        "ingestion": _obj(open_=True),
    })


def _session_retention_schema() -> dict:
    return _obj({
        "hot_ttl_s": _NUM,
        "warm_ttl_s": _NUM,
        "cold_ttl_s": _NUM,
        "purgeDeleted": _BOOL,
    })


def _arena_job_schema() -> dict:
    # scenarios/scenariosFrom are an either-or (admission enforces it);
    # requiring scenarios here would reject every source-fed job.
    return _obj({
        "scenarios": _arr(_obj({
            "name": _str(),
            "turns": _arr(_obj(open_=True)),
            "checks": _arr(_obj(open_=True)),
        }, required=["name"], open_=True)),
        "scenariosFrom": _obj({
            "name": _str(),
            "path": _str(),
        }, required=["name"]),
        "providers": _arr(_str()),
        "repeats": _INT,
        "mode": _str(enum=("direct", "fleet")),
        "threshold": _obj({
            "min_pass_rate": _NUM,
            "max_error_rate": _NUM,
            "max_p95_latency_s": _NUM,
            # Simulator SLO gates (evals/trafficsim → Aggregator
            # add_slo_cells): attainment + flight-recorder percentiles.
            "min_slo_attainment": _NUM,
            "max_p95_ttft_ms": _NUM,
            "max_p95_itl_ms": _NUM,
        }),
    }, required=["providers"])


def _tool_policy_schema() -> dict:
    return _obj({
        "tools": _arr(_str()),
        "agents": _arr(_str()),
        "rules": _arr(_obj({
            "action": _str(enum=("allow", "deny")),
            "when": _str(),
            "reason": _str(),
        }, required=["action"])),
        "default_action": _str(enum=("allow", "deny")),
        "priority": _INT,
    }, required=["rules"])


def _session_privacy_policy_schema() -> dict:
    return _obj({
        # Compliance preset expanded server-side (ee/pkg/compliance).
        "preset": _str(enum=("gdpr", "hipaa", "ccpa")),
        "recording": _BOOL,
        "redactFields": _arr(_str()),
        "consentCategories": _arr(_str()),
        "retention": _obj(open_=True),
        "userOptOut": _obj(open_=True),
        "encryption": _obj(open_=True),
    })


def _rollout_analysis_schema() -> dict:
    return _obj({
        "metrics": _arr(_obj({
            "name": _str(),
            "threshold": _NUM,
            "maxErrorRate": _NUM,
            "maxP95LatencyS": _NUM,
        }, required=["name"])),
        "interval_s": _NUM,
    }, required=["metrics"])


def _skill_source_schema() -> dict:
    return _obj({
        "source": _obj({
            "type": _str(enum=("dir", "configmap", "git", "oci")),
            "path": _str(),
            "ref": _str(),
            "data": _obj(open_=True, desc="configmap payload {filename: text}"),
        }, required=["type"]),
        "interval_s": _NUM,
    }, required=["source"])


# Shared source shape for PromptPackSource / Arena*Source (reference
# sourcesync_types.go:56-58: git | oci | configmap; local for devroots).
def _sync_source() -> dict:
    return _obj({
        "type": _str(enum=("git", "oci", "configmap", "local")),
        "repo": _str(desc="git clone url"),
        "ref": _str(desc="git branch/tag, or OCI host/repo:tag[@digest]"),
        "path": _str(),
        "data": _obj(open_=True, desc="configmap payload {filename: text}"),
        "token": _str(desc="OCI bearer token"),
    }, required=["type"])


def _prompt_pack_source_schema() -> dict:
    return _obj({
        "source": _sync_source(),
        "packName": _str(desc="target PromptPack name (default: source name)"),
        "packFile": _str(desc="pack JSON filename in the source (default pack.json)"),
        "interval_s": _NUM,
    }, required=["source"])


def _arena_source_schema() -> dict:
    return _obj({
        "source": _sync_source(),
        "interval_s": _NUM,
    }, required=["source"])


def _arena_dev_session_schema() -> dict:
    return _obj({
        "agentRef": _REF,
        "ttl_s": _NUM,
        "packOverride": _obj(open_=True),
    }, required=["agentRef"])


# kind → (plural, schema builder, short names)
KINDS: dict[str, tuple[str, object, list[str]]] = {
    "AgentRuntime": ("agentruntimes", _agent_runtime_schema, ["ar"]),
    "Provider": ("providers", _provider_schema, ["prov"]),
    "PromptPack": ("promptpacks", _prompt_pack_schema, ["pack"]),
    "ToolRegistry": ("toolregistries", _tool_registry_schema, ["tools"]),
    "Workspace": ("workspaces", _workspace_schema, ["ws"]),
    "AgentPolicy": ("agentpolicies", _agent_policy_schema, []),
    "MemoryPolicy": ("memorypolicies", _memory_policy_schema, []),
    "SessionRetentionPolicy": (
        "sessionretentionpolicies", _session_retention_schema, ["srp"],
    ),
    "SkillSource": ("skillsources", _skill_source_schema, []),
    # EE kinds (reference ee/api/v1alpha1).
    "ArenaJob": ("arenajobs", _arena_job_schema, ["aj"]),
    "ToolPolicy": ("toolpolicies", _tool_policy_schema, []),
    "SessionPrivacyPolicy": (
        "sessionprivacypolicies", _session_privacy_policy_schema, ["spp"],
    ),
    "RolloutAnalysis": ("rolloutanalyses", _rollout_analysis_schema, []),
    "PromptPackSource": ("promptpacksources", _prompt_pack_source_schema, ["pps"]),
    "ArenaSource": ("arenasources", _arena_source_schema, []),
    "ArenaTemplateSource": ("arenatemplatesources", _arena_source_schema, []),
    "ArenaDevSession": ("arenadevsessions", _arena_dev_session_schema, ["ads"]),
}


def render_crd(kind: str) -> dict:
    plural, schema_fn, short = KINDS[kind]
    return {
        "apiVersion": "apiextensions.k8s.io/v1",
        "kind": "CustomResourceDefinition",
        "metadata": {"name": f"{plural}.{GROUP}"},
        "spec": {
            "group": GROUP,
            "names": {
                "kind": kind,
                "listKind": f"{kind}List",
                "plural": plural,
                "singular": kind.lower(),
                **({"shortNames": short} if short else {}),
            },
            "scope": "Namespaced",
            "versions": [
                {
                    "name": VERSION,
                    "served": True,
                    "storage": True,
                    "subresources": {"status": {}},
                    "schema": {
                        "openAPIV3Schema": _obj({
                            "apiVersion": _str(),
                            "kind": _str(),
                            "metadata": {"type": "object"},
                            "spec": schema_fn(),
                            "status": _obj(open_=True),
                        }),
                    },
                }
            ],
        },
    }


def render_crds() -> list[dict]:
    return [render_crd(kind) for kind in KINDS]
