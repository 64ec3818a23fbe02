"""Multi-host initialization: the DCN control plane under the mesh.

In-pod communication is XLA collectives over ICI (sharding.py, ring.py);
spanning hosts needs ``jax.distributed`` — a gRPC coordinator that lets
every process see the global device set, after which the same Mesh/pjit
programs run unchanged with XLA routing intra-pod traffic over ICI and
cross-pod over DCN (SURVEY.md §2.4: this replaces the reference's HTTP
fan-out as the scale-out fabric).

``initialize()`` is env-driven so launchers only set three variables:

  FEI_TPU_COORDINATOR   host:port of process 0 (also accepts the standard
                        JAX_COORDINATOR_ADDRESS)
  FEI_TPU_NUM_PROCESSES world size
  FEI_TPU_PROCESS_ID    this process's rank

On TPU pods with standard tooling, pod launcher markers
(TPU_WORKER_HOSTNAMES / CLOUD_TPU_TASK_ID / MEGASCALE_*) are present and
``initialize()`` with no env set delegates to JAX's cluster auto-detection;
with neither explicit config nor pod markers it is a documented no-op, so
single-host code paths never probe metadata services.
"""

from __future__ import annotations

import os

import jax

from fei_tpu.utils.logging import get_logger

log = get_logger("parallel.distributed")

_initialized = False


def is_initialized() -> bool:
    return _initialized


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Join (or skip) the multi-host cluster. Returns True if distributed
    mode is active after the call. Idempotent."""
    global _initialized
    if _initialized:
        return True
    coordinator_address = (
        coordinator_address
        or os.environ.get("FEI_TPU_COORDINATOR")
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
    )
    env_np = os.environ.get("FEI_TPU_NUM_PROCESSES")
    env_pid = os.environ.get("FEI_TPU_PROCESS_ID")
    if num_processes is None and env_np is not None:
        num_processes = int(env_np)
    if process_id is None and env_pid is not None:
        process_id = int(env_pid)

    auto_detect = coordinator_address is None and num_processes is None
    if auto_detect:
        # No explicit config. Delegate to JAX's own cluster auto-detection
        # only when pod launcher markers are present — attempting it on a
        # plain single host would probe metadata services and hang/fail.
        pod_markers = (
            "TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID",
            "MEGASCALE_COORDINATOR_ADDRESS",
        )
        if not any(m in os.environ for m in pod_markers):
            log.debug("no coordinator configured; staying single-host")
            return False
    _enable_cpu_collectives()
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
        log.info(
            "distributed: process %d/%d, %d global / %d local devices",
            jax.process_index(), jax.process_count(),
            len(jax.devices()), len(jax.local_devices()),
        )
        return True
    except Exception as exc:  # noqa: BLE001
        if auto_detect:
            # pod markers present but no detectable cluster (e.g. a dev box
            # with leftover env): downgrade to single-host, don't crash
            log.warning("cluster auto-detect failed (%s); single-host", exc)
            return False
        log.error("jax.distributed.initialize failed: %s", exc)
        raise


def _enable_cpu_collectives() -> None:
    """Multi-process runs on the CPU backend (the two-rank rehearsal
    tests, TPU-less dev boxes) need a real cross-process collectives
    implementation: the default CPU client has none, so any computation
    touching a multi-host sharding fails with "Multiprocess computations
    aren't implemented on the CPU backend". jaxlib ships a gloo transport
    behind ``jax_cpu_collectives_implementation`` — turn it on before the
    backend is created when the platform is explicitly CPU (TPU/GPU paths
    are untouched)."""
    platform = (
        os.environ.get("JAX_PLATFORMS", "")
        or str(getattr(jax.config, "jax_platforms", "") or "")
    )
    if platform.startswith("cpu"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def process_info() -> dict:
    """This process's view of the cluster (works single-host too)."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "distributed": _initialized,
    }
