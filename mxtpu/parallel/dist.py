"""Multi-process bootstrap — the rebuild of ps-lite's rendezvous
(reference ``3rdparty/ps-lite/src/postoffice.cc`` Postoffice::Start,
``tools/launch.py`` DMLC_* env protocol [path cite], SURVEY.md §2.5).

The reference wired scheduler/server/worker roles through DMLC_* env
vars; the TPU-native design has one role (worker) and a coordinator,
via ``jax.distributed.initialize``. For compatibility, DMLC_* variables
are honored as aliases so reference launch scripts keep working:

  DMLC_PS_ROOT_URI:PORT → coordinator_address
  DMLC_NUM_WORKER       → num_processes
  DMLC_WORKER_ID        → process_id
"""
from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = ["initialize", "is_initialized", "process_index", "process_count",
           "local_devices", "shutdown"]

_initialized = False
_client_started = False   # whether jax.distributed.initialize() actually ran


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids=None) -> None:
    """Join the multi-host job. No-op if single-process (the common case
    on one host: jax already sees all local devices)."""
    global _initialized
    if _initialized:
        return
    if coordinator_address is None:
        uri = os.environ.get("DMLC_PS_ROOT_URI")
        port = os.environ.get("DMLC_PS_ROOT_PORT", "9091")
        if uri:
            coordinator_address = f"{uri}:{port}"
    if num_processes is None and "DMLC_NUM_WORKER" in os.environ:
        num_processes = int(os.environ["DMLC_NUM_WORKER"])
    if process_id is None and "DMLC_WORKER_ID" in os.environ:
        process_id = int(os.environ["DMLC_WORKER_ID"])
    if coordinator_address is None and num_processes in (None, 1):
        _initialized = True  # single-process: nothing to rendezvous
        return
    # CPU multi-process needs the cross-process collectives impl
    # (gloo) — without it every process stays a world of its own
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids)
    # pin this process's computations to ITS device: otherwise
    # uncommitted arrays jit onto global device 0 and every other rank
    # holds non-addressable results
    jax.config.update("jax_default_device", jax.local_devices()[0])
    global _client_started
    _client_started = True
    _initialized = True


def is_initialized() -> bool:
    return _initialized


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def local_devices():
    return jax.local_devices()


def shutdown() -> None:
    global _initialized, _client_started
    if _client_started:
        jax.distributed.shutdown()
    _client_started = False
    _initialized = False
