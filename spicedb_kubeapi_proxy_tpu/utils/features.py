"""Feature-gate registry (reference pkg/proxy/features.go:10-27).

The reference registers the component-base logging gates
(LoggingAlphaOptions/LoggingBetaOptions/ContextualLogging) into a mutable
gate map consulted at runtime.  This build keeps the same contract: named
boolean gates with a maturity stage and default, settable from the CLI
(`--feature-gates name=true,other=false`) or programmatically, consulted
via `enabled()`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

ALPHA = "ALPHA"
BETA = "BETA"
GA = "GA"


class FeatureGateError(ValueError):
    pass


@dataclass
class _Gate:
    name: str
    stage: str
    default: bool
    value: bool


class FeatureGates:
    def __init__(self):
        self._gates: Dict[str, _Gate] = {}

    def register(self, name: str, stage: str = ALPHA,
                 default: bool = False) -> None:
        if name in self._gates:
            raise FeatureGateError(f"feature gate {name!r} already registered")
        self._gates[name] = _Gate(name, stage, default, default)

    def enabled(self, name: str) -> bool:
        gate = self._gates.get(name)
        if gate is None:
            raise FeatureGateError(f"unknown feature gate {name!r}")
        return gate.value

    def set(self, name: str, value: bool) -> None:
        gate = self._gates.get(name)
        if gate is None:
            raise FeatureGateError(f"unknown feature gate {name!r}")
        gate.value = value

    def apply_flag(self, spec: str) -> None:
        """Parse a `name=true,name2=false` CLI value (component-base
        syntax; a bare name means true)."""
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            name, _, raw = part.partition("=")
            raw = raw.strip().lower() or "true"
            if raw not in ("true", "false"):
                raise FeatureGateError(
                    f"invalid feature gate value {part!r}: want name=true|false")
            self.set(name.strip(), raw == "true")

    def known(self) -> dict:
        return {g.name: (g.stage, g.value) for g in self._gates.values()}

    def reset(self) -> None:
        for g in self._gates.values():
            g.value = g.default


# process-wide gates, mirroring the reference's global gate map
GATES = FeatureGates()

# logging gates the reference registers (features.go:17-26)
GATES.register("ContextualLogging", stage=ALPHA, default=True)
GATES.register("LoggingAlphaOptions", stage=ALPHA, default=False)
GATES.register("LoggingBetaOptions", stage=BETA, default=True)
# build-specific gates
GATES.register("StructuredRequestLog", stage=BETA, default=True)
GATES.register("CrossRequestBatching", stage=GA, default=True)
# revision-keyed decision cache with relation-scoped invalidation
# (spicedb/decision_cache.py); also switchable per endpoint via
# `?cache=1` or the --decision-cache CLI flag
GATES.register("DecisionCache", stage=ALPHA, default=False)
# durable relationship store (spicedb/persist): WAL + checkpoints +
# crash recovery; engages when --data-dir is set, this gate is the
# killswitch (disable to run in-memory despite a configured data dir)
GATES.register("DurableStore", stage=BETA, default=True)
# device telemetry & flight recorder (utils/devtel.py): HBM ledger,
# kernel/compile accounting, batch occupancy, SLO burn rates; this gate
# is the killswitch for recording + the flight-recorder window task
GATES.register("DeviceTelemetry", stage=BETA, default=True)
# dispatch timeline profiler (utils/timeline.py): bounded event ring,
# chrome-trace export at /debug/timeline, transfer/compute overlap +
# roofline + stall attribution; this gate is the killswitch for
# recording (span() degrades to a shared no-op context)
GATES.register("Timeline", stage=BETA, default=True)
# device-resident query pipeline (ops/ell.py, ops/spmv.py,
# ops/jax_endpoint.py, spicedb/dispatch.py): on-device bitplane
# word-transpose, donated per-bucket state arenas, async D2H readback,
# and depth-N double-buffered fused dispatch (--pipeline-depth).  This
# gate is the killswitch: off reproduces the pre-pipeline serial path
# (host word-transpose, blocking device sync, single-slot lookup window)
GATES.register("DevicePipeline", stage=BETA, default=True)
# off-loop incremental rebuilds (ops/jax_endpoint.py): device-graph
# rebuilds run on a background executor against a store snapshot while
# the old generation keeps serving (queries on pairs the old graph can
# no longer answer route to the host oracle), then swap atomically
# under a short lock.  This gate is the killswitch: off reproduces the
# pre-PR synchronous rebuild-under-lock behavior exactly.
GATES.register("AsyncRebuild", stage=BETA, default=True)
# admission control (utils/admission.py, spicedb/dispatch.py,
# proxy/server.py): bounded dispatcher queues + read-only load shedding
# with 429/Retry-After.  This gate is the killswitch: off, configured
# bounds and shed thresholds are inert and overload queues unboundedly
# as before.
GATES.register("AdmissionControl", stage=BETA, default=True)
# WAL-shipping read replicas (spicedb/replication, docs/replication.md):
# leader-side replication API (/replication/*) + follower mode
# (--replicate-from).  This gate is the killswitch: off, the replication
# routes are not served and a configured --replicate-from is inert —
# exactly today's single-node behavior.
GATES.register("Replication", stage=ALPHA, default=True)
# differential fuzz-harness telemetry (fuzz/metrics.py): authz_fuzz_*
# counters recorded by the offline harness (scripts/fuzz_smoke.py,
# budgeted campaigns).  This gate is the killswitch for the recording
# helpers; off, fuzz runs tick nothing.
GATES.register("FuzzTelemetry", stage=ALPHA, default=True)
# partitioned write scale-out (spicedb/sharding, docs/replication.md
# "Sharding"): footprint-proven tuple-space sharding across N leaders
# with a thin router and revision-vector ZedTokens.  This gate is the
# killswitch: off, --shards/--partition-map are inert (single-shard
# behavior exactly), the router degrades to a pass-through to the
# default shard, and the authz_shard_* metrics tick nothing.
GATES.register("Sharding", stage=ALPHA, default=True)
# kernel introspection & workload cost attribution (ops/ell.py,
# ops/spmv.py, utils/workload.py): measured sweep-iteration counters and
# per-iteration frontier-population traces threaded through the fixpoint
# carry, read back with the existing result D2H; feeds
# authz_sweep_iterations / authz_frontier_decay and the per-(type,
# permission) /debug/workload attribution rows, and upgrades the
# timeline roofline from modeled one-sweep bytes to measured
# iterations x per-sweep bytes.  This gate is the killswitch: off, the
# kernels build exactly the pre-introspection jitted functions
# (byte-identical carry shape), no sweep metrics tick, and the roofline
# keeps its modeled lower-bound semantics.
GATES.register("KernelIntrospect", stage=BETA, default=True)
# on-demand sampling profiler (utils/profiler.py): authed
# /debug/profile?seconds=N thread sampler with collapsed-stack and
# chrome-trace output.  This gate is the killswitch: off, capture
# requests are refused and the sampler thread never starts.
GATES.register("Profiler", stage=ALPHA, default=True)
# multi-chip mesh execution (parallel/sharding.py,
# ops/jax_endpoint.py _ShardedEllGraph): 2D (data x graph) shard_map
# kernels behind `jax://?mesh=...` — row-sharded ELL tables with
# per-iteration tiled all_gather, word-sharded batches, sharded donated
# state arenas, and per-device HBM ledger rows.  This gate is the
# killswitch: off, `mesh=auto` degrades to the single-chip kernels
# (byte-identical single-device path) and an explicit `mesh=DxG` fails
# endpoint construction loudly (an authz proxy must not silently ignore
# an explicitly configured topology).
GATES.register("MeshExecution", stage=ALPHA, default=True)
# Leopard-style materialized group index (ops/leopard.py,
# ops/jax_endpoint.py): statically-proven group-membership fragments are
# flattened into device-resident transitive-closure bitplanes consulted
# before the iterative kernel (one AND+popcount instead of one fixpoint
# iteration per nesting level), maintained incrementally from store
# deltas with delete-quarantine + background re-close.  This gate is the
# killswitch: off, no closure is planned or built and the check/lookup
# ladders are byte-identical to the pre-index build.  The gate is
# evaluated at endpoint construction (like a configured mesh): flipping
# it mid-process affects endpoints built afterwards.
GATES.register("LeopardIndex", stage=ALPHA, default=True)
# tail explainer (utils/tailexplain.py): /debug/tail report diffing the
# p99 trace population against the p50 population of the merged fleet
# view into a ranked per-(tier, serving stage) "where the tail lives"
# breakdown.  This gate is the killswitch: off, /debug/tail answers
# enabled:false and no report is computed — trace collection itself is
# governed by the existing Timeline/fleet plumbing, not this gate.
GATES.register("TailExplain", stage=BETA, default=True)


def mesh_enabled() -> bool:
    """MeshExecution gate accessor; unknown-gate errors fail open so an
    embedded user with a stripped gate registry keeps a configured
    mesh (mirrors pipeline_enabled below)."""
    try:
        return GATES.enabled("MeshExecution")
    except Exception:
        return True


def pipeline_enabled() -> bool:
    """DevicePipeline gate accessor; unknown-gate errors fail open so
    embedded users with a stripped gate registry still get the fast
    path (mirrors utils/timeline.enabled)."""
    try:
        return GATES.enabled("DevicePipeline")
    except Exception:
        return True


def leopard_enabled() -> bool:
    """LeopardIndex gate accessor; unknown-gate errors fail CLOSED —
    unlike the mesh/pipeline accessors, the safe degraded mode for a
    stripped registry is the iterative kernel (no index is always
    correct, it is only slower)."""
    try:
        return GATES.enabled("LeopardIndex")
    except Exception:
        return False
