"""Endpoint plugin boundary: `embedded://` | `grpc://` | `jax://`.

Mirrors the reference's SpiceDB-endpoint dispatch on URL scheme
(reference pkg/proxy/options.go:307-369): upper layers (authz middleware,
dual-write engine) speak only this interface — the seven verbs the proxy
consumes (SURVEY.md §5) — and never know which backend ran.

- `embedded://`       host tuple store + recursive evaluator (the oracle);
                      replaces the reference's in-process SpiceDB
                      (pkg/spicedb/spicedb.go:18-71)
- `jax://`            same store, but check/LookupResources execute as
                      batched boolean-SpMV reachability kernels on TPU
- `grpc://host:port`  remote SpiceDB (requires grpcio; optional)
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass
from typing import Any, Iterable, Optional
from urllib.parse import urlsplit

import yaml

from . import schema as sch
from .evaluator import Evaluator
from .store import TupleStore, Watcher
from .types import (
    AnnotatedIds,
    CheckRequest,
    CheckResult,
    Permissionship,
    Precondition,
    RelationshipFilter,
    RelationshipUpdate,
    SchemaError,
    SubjectRef,
    parse_relationship,
)

logger = logging.getLogger("spicedb_kubeapi_proxy_tpu.spicedb")

_YAML_LOADER = getattr(yaml, "CSafeLoader", yaml.SafeLoader)


def apply_bootstrap_once(store: TupleStore, rel_text: str) -> bool:
    """Bootstrap-once semantics shared by the store-backed endpoints:
    `--spicedb-bootstrap` relationships apply only to a store with no
    history (revision 0).  A store recovered from a data dir
    (spicedb/persist) carries a revision > 0, so a restart never
    double-applies bootstrap writes on top of recovered state."""
    if not rel_text.strip():
        return False
    if store.revision > 0:
        logger.info(
            "skipping bootstrap relationships: store already carries "
            "state at revision %d (recovered from a data dir)",
            store.revision)
        return False
    # columnar bulk path (native parser when available)
    t0 = time.perf_counter()
    store.bulk_load_text(rel_text)
    logger.info("loaded bootstrap relationships in %.2fs",
                time.perf_counter() - t0)
    return True


class PermissionsEndpoint:
    """The endpoint contract (PermissionsService + WatchService subset)."""

    def _validate_updates(self, updates: Iterable[RelationshipUpdate]) -> list:
        """Schema-validate writes (SpiceDB WriteRelationships semantics)
        for any endpoint that carries a schema; shared by the embedded and
        jax backends so the rule set cannot diverge."""
        updates = list(updates)
        schema = getattr(self, "schema", None)
        if schema is not None:
            for u in updates:
                sch.validate_relationship(schema, u.rel)
        return updates

    async def check_permission(self, req: CheckRequest) -> CheckResult:
        raise NotImplementedError

    async def check_bulk_permissions(self, reqs: list) -> list:
        raise NotImplementedError

    async def lookup_resources(self, resource_type: str, permission: str,
                               subject: SubjectRef) -> list:
        raise NotImplementedError

    async def lookup_resources_batch(self, resource_type: str, permission: str,
                                     subjects: list) -> list:
        """One allowed-id list per subject.  Backends that can batch (jax://)
        fuse the whole batch into a single kernel invocation."""
        return [await self.lookup_resources(resource_type, permission, s)
                for s in subjects]

    async def lookup_resources_stream(self, resource_type: str,
                                      permission: str, subject: SubjectRef):
        """Async iterator of allowed resource ids (the reference drains the
        LookupResources gRPC server-stream incrementally, lookups.go:74-135,
        so per-result extraction overlaps transfer).  Default: wrap the
        materialized list; `grpc://` overrides with the real stream and
        `jax://` yields device->host chunks."""
        for rid in await self.lookup_resources(resource_type, permission,
                                               subject):
            yield rid

    async def read_relationships(self, flt: RelationshipFilter) -> list:
        raise NotImplementedError

    async def read_relationships_stream(self, flt: RelationshipFilter):
        """Async iterator of relationships (reference activity.go:160-172
        drains a server-stream).  Default wraps the materialized list."""
        for rel in await self.read_relationships(flt):
            yield rel

    async def write_relationships(self, updates: Iterable[RelationshipUpdate],
                                  preconditions: Iterable[Precondition] = ()) -> int:
        raise NotImplementedError

    async def delete_relationships(self, flt: RelationshipFilter,
                                   preconditions: Iterable[Precondition] = ()) -> int:
        raise NotImplementedError

    def watch(self, object_types: Optional[Iterable[str]] = None) -> Watcher:
        raise NotImplementedError

    async def close(self) -> None:
        pass


@dataclass
class Bootstrap:
    schema_text: str = ""
    relationships_text: str = ""

    @classmethod
    def from_mapping(cls, data: dict) -> "Bootstrap":
        return cls(schema_text=data.get("schema", "") or "",
                   relationships_text=data.get("relationships", "") or "")

    @classmethod
    def from_yaml(cls, content: str) -> "Bootstrap":
        # libyaml's loader where built: a 1M-tuple relationships block
        # parses in ~0.3 s there against ~15 s in pure Python
        data = yaml.load(content, Loader=_YAML_LOADER) or {}
        if not isinstance(data, dict):
            raise ValueError("bootstrap content must be a YAML mapping")
        return cls.from_mapping(data)

    @classmethod
    def from_file(cls, path: str) -> "Bootstrap":
        with open(path, "r", encoding="utf-8") as f:
            return cls.from_yaml(f.read())

    def relationships(self) -> list:
        rels = []
        for line in self.relationships_text.splitlines():
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            rels.append(parse_relationship(line))
        return rels


# The proxy's own definitions (dual-write locks, workflow idempotency keys)
# are merged into every user-supplied bootstrap schema — the reference
# always loads its embedded bootstrap.yaml into embedded SpiceDB alongside
# user content (spicedb.go:63-67), so lock/workflow tuples validate there
# regardless of the user's schema.
INTERNAL_SCHEMA = """
use expiration

definition lock {
  relation workflow: workflow
}

definition workflow {
  relation idempotency_key: activity with expiration
}

definition activity {}
"""

# The default bootstrap schema applied when none is supplied: the proxy's own
# workflow/lock/idempotency definitions plus the demo cluster/namespace/pod
# types (behavioral equivalent of the reference's embedded bootstrap.yaml).
DEFAULT_BOOTSTRAP_SCHEMA = INTERNAL_SCHEMA + """
definition cluster {}
definition user {}
definition namespace {
  relation cluster: cluster
  relation creator: user
  relation viewer: user

  permission admin = creator
  permission edit = creator
  permission view = viewer + creator
  permission no_one_at_all = nil
}
definition pod {
  relation namespace: namespace
  relation creator: user
  relation viewer: user
  permission edit = creator
  permission view = viewer + creator
}
definition testresource {
  relation namespace: namespace
  relation creator: user
  relation viewer: user
  permission edit = creator
  permission view = viewer + creator
}
"""


def merge_internal_definitions(schema: "sch.Schema") -> "sch.Schema":
    """Add the proxy-internal definitions to `schema`.  A user definition
    reusing one of the internal type names must carry the relations the
    dual-write engine writes — otherwise every update rule would fail at
    runtime once write validation runs — so collisions that drop an
    internal relation are a loud bootstrap error, not a silent shadow."""
    internal = sch.parse_schema(INTERNAL_SCHEMA)
    for name, d in internal.definitions.items():
        existing = schema.definitions.get(name)
        if existing is None:
            schema.definitions[name] = d
            continue
        # the user's redefinition must accept every subject-type annotation
        # the engine writes (same relation name is not enough: `relation
        # workflow: user` would still reject lock tuples at runtime)
        missing = [
            f"{rel}: {ref.type}"
            for rel, refs in d.relations.items()
            for ref in refs
            if ref not in (existing.relations.get(rel) or ())
        ]
        if missing:
            raise SchemaError(
                f"definition `{name}` is reserved for the proxy's dual-write "
                f"engine; a bootstrap schema may redefine it only if it "
                f"keeps the engine's relation annotations (missing: "
                f"{missing})")
    if "expiration" not in schema.uses:
        schema.uses = tuple(schema.uses) + ("expiration",)
    return schema


class EmbeddedEndpoint(PermissionsEndpoint):
    """Host tuple store + recursive evaluator (`embedded://`)."""

    def __init__(self, schema: sch.Schema, store: Optional[TupleStore] = None):
        self.schema = schema
        self.store = store if store is not None else TupleStore()
        self.evaluator = Evaluator(schema, self.store)

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_bootstrap(cls, bootstrap: Optional[Bootstrap] = None,
                       store: Optional[TupleStore] = None) -> "EmbeddedEndpoint":
        if bootstrap is None or not bootstrap.schema_text:
            schema_text = DEFAULT_BOOTSTRAP_SCHEMA
            rel_text = bootstrap.relationships_text if bootstrap else ""
        else:
            schema_text = bootstrap.schema_text
            rel_text = bootstrap.relationships_text
        endpoint = cls(merge_internal_definitions(sch.parse_schema(schema_text)),
                       store=store)
        apply_bootstrap_once(endpoint.store, rel_text)
        return endpoint

    # -- verbs --------------------------------------------------------------

    _TRISTATE = {0: Permissionship.NO_PERMISSION,
                 1: Permissionship.CONDITIONAL_PERMISSION,
                 2: Permissionship.HAS_PERMISSION}

    def _check_sync(self, req: CheckRequest) -> CheckResult:
        # evaluation + the checked_at revision read are ONE atomic unit
        # under the store lock (reentrant, so the bulk wrapper's outer
        # hold still gives one revision per bulk): writes commit from
        # executor threads now, and an unlocked revision read could
        # stamp a verdict with a revision the evaluation never saw —
        # a replica honoring that ZedToken would serve it as fresh
        with self.store.lock:
            value = self.evaluator.check3(req.resource, req.permission,
                                          req.subject)
            return CheckResult(
                permissionship=self._TRISTATE[value],
                checked_at=self.store.revision,
                source="oracle",
            )

    def _check_bulk_sync(self, reqs: list) -> list:
        # one revision per bulk: writes commit from executor threads
        # (see write_relationships below), so the bulk snapshots under
        # the store lock — the same no-torn-bulk contract the jax
        # endpoint keeps with its capture lock
        with self.store.lock:
            return [self._check_sync(r) for r in reqs]

    def _lookup_sync(self, resource_type: str, permission: str,
                     subject: SubjectRef) -> list:
        # the oracle lookup enumerates candidates and checks each; a
        # write landing mid-enumeration would yield a result correct at
        # NO single revision — hold the lock for the whole pass (the
        # pre-executor behavior, where loop serialization implied it)
        with self.store.lock:
            return self.evaluator.lookup_resources(resource_type,
                                                   permission, subject)

    # Store-touching verbs hop to an executor: the evaluator's reads
    # contend on the store lock, which a concurrent committing writer
    # holds ACROSS the WAL append + fsync — a loop-side acquire would
    # park the whole loop for that disk barrier (analyzer A001 class).

    async def check_permission(self, req: CheckRequest) -> CheckResult:
        return await asyncio.get_running_loop().run_in_executor(
            None, self._check_sync, req)

    async def check_bulk_permissions(self, reqs: list) -> list:
        return await asyncio.get_running_loop().run_in_executor(
            None, self._check_bulk_sync, reqs)

    async def lookup_resources(self, resource_type: str, permission: str,
                               subject: SubjectRef) -> list:
        ids = await asyncio.get_running_loop().run_in_executor(
            None, self._lookup_sync, resource_type, permission, subject)
        return AnnotatedIds(ids, source="oracle")

    async def read_relationships(self, flt: RelationshipFilter) -> list:
        return await asyncio.get_running_loop().run_in_executor(
            None, self.store.read, flt)

    async def write_relationships(self, updates: Iterable[RelationshipUpdate],
                                  preconditions: Iterable[Precondition] = ()) -> int:
        # the commit path journals synchronously (WAL append + fsync
        # under the durable store's policy) before becoming visible —
        # a disk barrier that must never park the event loop (analyzer
        # A001 class); the store lock serializes against every reader,
        # so the hop changes where the write blocks, not what it means
        ups = self._validate_updates(updates)
        pres = list(preconditions)
        return await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.store.write(ups, pres))

    async def delete_relationships(self, flt: RelationshipFilter,
                                   preconditions: Iterable[Precondition] = ()) -> int:
        pres = list(preconditions)
        rev, _ = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.store.delete_by_filter(flt, pres))
        return rev

    def watch(self, object_types: Optional[Iterable[str]] = None) -> Watcher:
        return self.store.subscribe(object_types)


class EndpointConfigError(ValueError):
    pass


def _resolve_cache_config(url: str, params: dict, kwargs: dict):
    """Decision-cache wiring decision for create_endpoint: the explicit
    kwarg (CLI --decision-cache) or the `?cache=1` URL param or the
    DecisionCache feature gate turns it on; returns
    (enabled, explicit, max_bytes) after POPPING the cache kwargs so
    backend constructors never see them.  `explicit` distinguishes a
    user-requested cache (refusing it is an error) from a gate-derived
    default (silently inapplicable for store-less backends)."""
    want = kwargs.pop("decision_cache", None)
    max_bytes = kwargs.pop("decision_cache_bytes", None)
    explicit = want is not None
    raw = (params.get("cache") or [""])[0].lower()
    if want is None:
        if raw in ("1", "true", "yes"):
            want, explicit = True, True
        elif raw in ("0", "false", "no"):
            want, explicit = False, True
        elif raw == "":
            from ..utils.features import GATES
            want = GATES.enabled("DecisionCache")
        else:
            raise EndpointConfigError(
                f"invalid cache={raw!r} in {url!r} "
                f"(expected 1/true/yes/0/false/no)")
    raw_bytes = (params.get("cache_bytes") or [""])[0]
    if raw_bytes:
        try:
            max_bytes = int(raw_bytes)
        except ValueError as e:
            raise EndpointConfigError(
                f"invalid cache_bytes in {url!r}: {e}") from e
    return bool(want), explicit, max_bytes


def _wrap_decision_cache(ep: PermissionsEndpoint,
                         max_bytes: Optional[int]) -> PermissionsEndpoint:
    from .decision_cache import DEFAULT_MAX_BYTES, DecisionCacheEndpoint
    return DecisionCacheEndpoint(
        ep, max_bytes=max_bytes if max_bytes else DEFAULT_MAX_BYTES)


def create_endpoint(url: str,
                    bootstrap: Optional[Bootstrap] = None,
                    **kwargs: Any) -> PermissionsEndpoint:
    """Endpoint registry dispatching on URL scheme
    (reference options.go:307-369)."""
    from urllib.parse import parse_qs

    if "://" not in url and url:
        # scheme-less `host:port` is a remote SpiceDB, exactly like the
        # reference's default `localhost:50051` (options.go:107: anything
        # that isn't embedded:// dials gRPC; TLS unless --spicedb-insecure)
        url = "grpcs://" + url
    split = urlsplit(url)
    scheme = split.scheme
    params = parse_qs(split.query)
    cache_on, cache_explicit, cache_bytes = _resolve_cache_config(
        url, params, kwargs)
    # fused-dispatch pipeline depth (spicedb/dispatch.py): CLI flag via
    # kwargs, `jax://?pipeline_depth=N` overrides; popped here so the
    # non-batched schemes never see an unexpected kwarg
    pipeline_depth = kwargs.pop("pipeline_depth", None)
    # dispatcher queue bound (admission control, --max-queue-depth;
    # `jax://?max_queue_depth=N` overrides; 0 = unbounded)
    max_queue_depth = kwargs.pop("max_queue_depth", None)
    # a pre-built store (the persistence layer hands its recovered store
    # in here) only makes sense for the store-backed backends
    store = kwargs.pop("store", None)
    if scheme not in ("embedded", "jax") and store is not None:
        raise EndpointConfigError(
            f"--data-dir persistence requires a store-backed endpoint "
            f"(embedded:// or jax://), not {url!r}")
    if scheme not in ("embedded", "jax") and cache_on:
        if cache_explicit:
            raise EndpointConfigError(
                f"--decision-cache requires a store-backed endpoint "
                f"(embedded:// or jax://), not {url!r}")
        cache_on = False  # gate-derived default: inapplicable, not fatal
    if scheme == "embedded":
        ep = EmbeddedEndpoint.from_bootstrap(bootstrap, store=store)
        return _wrap_decision_cache(ep, cache_bytes) if cache_on else ep
    if scheme == "jax":
        from ..ops.jax_endpoint import JaxEndpoint  # lazy: pulls in jax
        # multi-host: `jax://?distributed=1` joins the jax.distributed
        # cluster named by the SPICEDB_TPU_COORDINATOR/NUM_PROCESSES/
        # PROCESS_ID env triplet (auto-detected on TPU pod slices) BEFORE
        # any mesh is built, so jax.devices() below is the global set and
        # the graph axis stripes across hosts over DCN.  `distributed=1`
        # is strict (an authz proxy must not silently fall back to a
        # partial device set); `distributed=auto` is best-effort so one
        # config spans single-host and pod deployments.
        dist_param = (params.get("distributed") or ["0"])[0].lower()
        if dist_param in ("1", "true", "yes", "auto"):
            from ..parallel.distributed import init_from_env
            try:
                init_from_env(strict=dist_param != "auto")
            except Exception as e:
                raise EndpointConfigError(
                    f"distributed={dist_param} in {url!r}: jax.distributed "
                    f"initialization failed: {e}") from e
        elif dist_param not in ("0", "false", "no", ""):
            raise EndpointConfigError(
                f"invalid distributed={dist_param!r} in {url!r} "
                f"(expected 1/true/yes/auto/0/false/no)")
        # multi-chip: `jax://?mesh=auto` shards the graph over all local
        # devices (2D data x graph mesh); `mesh=DxG` fixes the axis split.
        # Single-device processes fall back to the single-chip kernels.
        mesh_param = (params.get("mesh") or [""])[0]
        if mesh_param and "mesh" not in kwargs:
            from ..utils.features import mesh_enabled
            if not mesh_enabled():
                # MeshExecution killswitch: `auto` degrades to the
                # single-chip kernels (best-effort by definition), an
                # EXPLICIT topology must fail loudly rather than be
                # silently ignored
                if mesh_param != "auto":
                    raise EndpointConfigError(
                        f"mesh={mesh_param!r} in {url!r} requires the "
                        f"MeshExecution feature gate (disabled)")
            else:
                import jax

                from ..parallel.sharding import make_mesh
                if mesh_param == "auto":
                    if len(jax.devices()) > 1:
                        kwargs["mesh"] = make_mesh()
                else:
                    try:
                        data_s, _, graph_s = mesh_param.partition("x")
                        d, g = int(data_s), int(graph_s)
                        devices = jax.devices()
                        if d * g > len(devices):
                            raise ValueError(
                                f"mesh {d}x{g} needs {d * g} devices, "
                                f"have {len(devices)}")
                        # an explicit DxG smaller than the host takes
                        # the first d*g devices (run on a chip subset)
                        kwargs["mesh"] = make_mesh(devices[:d * g],
                                                   data=d, graph=g)
                    except ValueError as e:
                        raise EndpointConfigError(
                            f"invalid mesh {mesh_param!r} in {url!r}: {e}"
                        ) from e
        from ..utils.device import announce
        announce()
        if store is not None:
            kwargs["store"] = store
        ep: PermissionsEndpoint = JaxEndpoint.from_bootstrap(bootstrap,
                                                             **kwargs)
        # cross-request batched dispatch is on by default for the device
        # backend (`jax://?dispatch=direct` to bypass, or the
        # CrossRequestBatching feature gate); the batch IS the kernel
        # invocation (SURVEY.md §2 parallelism table)
        from ..utils.features import GATES
        default_dispatch = ("batched" if GATES.enabled("CrossRequestBatching")
                            else "direct")
        dispatch = (params.get("dispatch") or [default_dispatch])[0]
        if dispatch == "batched":
            from .dispatch import BatchingEndpoint
            try:
                max_batch = int((params.get("max_batch") or ["4096"])[0])
                if "pipeline_depth" in params:
                    pipeline_depth = int(params["pipeline_depth"][0])
                if "max_queue_depth" in params:
                    max_queue_depth = int(params["max_queue_depth"][0])
                ep = BatchingEndpoint(
                    ep, max_batch=max_batch,
                    pipeline_depth=(pipeline_depth
                                    if pipeline_depth is not None else 2),
                    max_queue_depth=(max_queue_depth
                                     if max_queue_depth is not None else 0))
            except ValueError as e:
                raise EndpointConfigError(
                    f"invalid max_batch/pipeline_depth/max_queue_depth "
                    f"in {url!r}: {e}") from e
        elif dispatch != "direct":
            raise EndpointConfigError(
                f"unknown dispatch mode {dispatch!r}; use batched|direct")
        if cache_on:
            # the cache sits ABOVE the dispatcher: a warm hit returns
            # before any queue/kernel work; misses flow through the fused
            # (singleflight-deduped) dispatch path and fill on return
            ep = _wrap_decision_cache(ep, cache_bytes)
        return ep
    if scheme in ("grpc", "grpcs", "http", "https"):
        # remote permissions service over gRPC (reference options.go:331-368:
        # TLS by default, bearer token, optional CA; `grpc`/`http` schemes or
        # insecure=True select plaintext)
        try:
            from .grpc_remote import RemoteEndpoint
        except ImportError as e:
            raise EndpointConfigError(
                f"remote endpoint {url!r} requires grpcio: {e}") from e
        target = split.netloc or split.path
        if not target:
            raise EndpointConfigError(f"remote endpoint {url!r} has no host")
        insecure = (scheme in ("grpc", "http")
                    or bool(kwargs.get("insecure")))
        ca_pem = None
        ca_path = kwargs.get("ca_path") or ""
        if ca_path:
            with open(ca_path, "rb") as f:
                ca_pem = f.read()
        return RemoteEndpoint(target, token=kwargs.get("token", ""),
                              insecure=insecure, ca_pem=ca_pem,
                              skip_verify=bool(kwargs.get("skip_verify")
                                               or kwargs.get("skip_verify_ca")))
    raise EndpointConfigError(f"unsupported spicedb endpoint scheme {scheme!r}")
