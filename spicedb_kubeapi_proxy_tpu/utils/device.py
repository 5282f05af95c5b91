"""The device a `jax://` process serves from.

Three things every entry point (CLI, permsd, bench.py, the smoke scripts)
must agree on, kept in one place:

- where JAX's persistent compilation cache lives (`configure_compile_cache`);
- which device the process reports it serves from (`announce`), logged at
  startup and exported as `authz_device_info{platform,device_kind}`;
- the published peak bandwidth of each device kind (`hbm_peak_gbps`), the
  denominator of every roofline fraction.  A kind missing from the table
  yields no roofline, never a borrowed number.

Importing this module does not import JAX; only `announce` does.
"""

from __future__ import annotations

import logging
import os
import sys
from typing import Optional

from . import metrics as m

logger = logging.getLogger("spicedb_kubeapi_proxy_tpu.device")

# the checkout root: spicedb_kubeapi_proxy_tpu/utils/device.py -> ../../
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Published HBM bandwidth per chip in GB/s, keyed by jax `device_kind`.
# Source: Google Cloud documentation, "TPU v5e" (16 GB HBM2, 819 GB/s).
HBM_PEAK_GBPS = {
    "TPU v5 lite": 819.0,
}

_unknown_logged: set = set()


def hbm_peak_gbps(device_kind: str) -> Optional[float]:
    """Published HBM peak of `device_kind`, or None (logged once per kind)
    when the table does not know it."""
    peak = HBM_PEAK_GBPS.get(device_kind)
    if peak is None and device_kind not in _unknown_logged:
        _unknown_logged.add(device_kind)
        logger.info("no published HBM peak for device kind %r: roofline "
                    "fractions are not computed", device_kind)
    return peak


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `.jax_cache/` in the
    checkout unless `JAX_COMPILATION_CACHE_DIR` is already set, which is
    then left alone.  Call from an entry point's main, before it compiles
    anything; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(REPO_ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax reads the variable when it is imported; a process that
        # already imported it needs the config set as well
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def announce(registry: Optional[m.Registry] = None) -> dict:
    """Log the platform, device kind and device count this process serves
    from and export them as `authz_device_info{platform,device_kind}` =
    count.  Imports JAX (and so initializes its backend)."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices)}
    (registry or m.REGISTRY).gauge(
        "authz_device_info",
        "Devices this process serves jax:// from (value = device count)",
        labels=("platform", "device_kind"),
    ).set(info["count"], platform=info["platform"],
          device_kind=info["device_kind"])
    logger.info("serving jax:// from %d %s device(s) (platform %s)",
                info["count"], info["device_kind"], info["platform"])
    return info
