"""Multi-host runtime glue: `jax.distributed` over DCN.

The reference scales across machines by pointing the proxy at a remote
SpiceDB over gRPC (reference pkg/proxy/options.go:331-368); this
framework's equivalents are (a) the `grpc://` endpoint + permsd for a
remote device-backed permission server, and (b) — TPU-natively — one
`jax://` endpoint spanning a MULTI-HOST device mesh: every proxy process
joins a `jax.distributed` cluster, `jax.devices()` becomes the global
device set, and the same 2D (data x graph) `shard_map` program from
parallel/sharding.py runs with the graph axis striped across hosts
(XLA routes per-iteration all_gathers over ICI within a slice and DCN
across slices — SURVEY.md §5 communication-backend note).

Environment contract (mirrors jax.distributed.initialize's arguments;
all three must be set together, or none for auto-detection on Cloud TPU
pods where the runtime provides them):

    SPICEDB_TPU_COORDINATOR   host:port of process 0
    SPICEDB_TPU_NUM_PROCESSES total process count
    SPICEDB_TPU_PROCESS_ID    this process's rank

Activate with `jax://?distributed=1&mesh=auto` (strict: endpoint
construction fails if the cluster cannot be joined — an authz proxy must
never silently degrade to a partial device set) or `distributed=auto`
(best-effort: single-host setups proceed standalone).
"""

from __future__ import annotations

import os
from typing import Optional


def _runtime_initialized() -> bool:
    """True when jax.distributed is already up in this process (whether
    or not this module did it)."""
    import jax

    return bool(jax.distributed.is_initialized())


def init_from_env(coordinator: Optional[str] = None,
                  num_processes: Optional[int] = None,
                  process_id: Optional[int] = None,
                  strict: bool = True) -> bool:
    """Join (or start) the jax.distributed cluster described by the
    SPICEDB_TPU_* env triplet / explicit arguments.  Idempotent against
    the real runtime state.  Returns True when the process is part of an
    initialized distributed runtime.

    `strict` governs the no-explicit-config auto-detect path: True
    re-raises initialization failures (a misconfigured pod worker must
    fail loudly, not serve answers over a partial mesh); False treats
    them as "not a cluster" and returns False."""
    if _runtime_initialized():
        return True
    import jax

    coordinator = coordinator or os.environ.get("SPICEDB_TPU_COORDINATOR")
    n_env = os.environ.get("SPICEDB_TPU_NUM_PROCESSES")
    p_env = os.environ.get("SPICEDB_TPU_PROCESS_ID")
    if num_processes is None and n_env:
        num_processes = int(n_env)
    if process_id is None and p_env:
        process_id = int(p_env)

    if coordinator is None and num_processes is None and process_id is None:
        # Cloud TPU pod slices auto-detect everything from the runtime's
        # own environment
        try:
            jax.distributed.initialize()
        except Exception:
            if strict:
                raise
            return False
        return True

    if not (coordinator and num_processes is not None
            and process_id is not None):
        raise ValueError(
            "partial multi-host config: SPICEDB_TPU_COORDINATOR, "
            "SPICEDB_TPU_NUM_PROCESSES and SPICEDB_TPU_PROCESS_ID must be "
            "set together")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def is_initialized() -> bool:
    return _runtime_initialized()
