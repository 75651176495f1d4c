"""Production meshes.  Functions only — importing this module never touches
jax device state (device count is locked at first jax init, and the dry-run
must set XLA_FLAGS before that happens).

Every mesh here is built with ``AxisType.Auto`` on every axis: the model
code places arrays with ``with_sharding_constraint`` and lets GSPMD
propagate, which ``jax.make_mesh``'s default ``Explicit`` axes refuse.

``set_scaleout_xla_flags`` appends the async-collective / latency-hiding
XLA options (the bayespec idiom from SNIPPETS.md) to ``XLA_FLAGS``; call it
before the first jax operation of the process or it cannot take effect.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

# Collective-overlap flags for multi-device training: async collectives run
# on their own stream and the latency-hiding scheduler moves them off the
# critical path, so the FSDP all-gather/reduce-scatter pairs and TP
# all-reduces overlap the matmuls that don't depend on them.  xla_gpu_*
# options are only registered in GPU jaxlib builds — a CPU-only build
# hard-fails on unknown XLA_FLAGS, so set_scaleout_xla_flags applies them
# only when a GPU platform is actually requested/visible.
SCALEOUT_XLA_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def _gpu_platform_requested() -> bool:
    plats = os.environ.get("JAX_PLATFORMS", os.environ.get("JAX_PLATFORM_NAME", ""))
    if plats:
        return any(p.strip() in ("gpu", "cuda", "rocm")
                   for p in plats.lower().split(","))
    # no explicit platform: GPU builds advertise through CUDA env/driver
    return bool(os.environ.get("CUDA_VISIBLE_DEVICES", "")) or os.path.exists(
        "/dev/nvidia0"
    )


def set_scaleout_xla_flags(extra: Sequence[str] = ()) -> str:
    """Append the scale-out flags (plus ``extra``) to ``XLA_FLAGS``,
    skipping any option already present; returns the resulting value.
    Must run before jax initializes its backend.  On CPU-only runs the
    xla_gpu_* set is skipped (unregistered flags are a fatal parse error
    there); ``extra`` is always applied."""
    current = os.environ.get("XLA_FLAGS", "")
    have = {f.split("=", 1)[0] for f in current.split() if f}
    wanted = (
        (*SCALEOUT_XLA_FLAGS, *extra) if _gpu_platform_requested()
        else tuple(extra)
    )
    add = [f for f in wanted if f.split("=", 1)[0] not in have]
    if add:
        current = " ".join(filter(None, [current, *add]))
        os.environ["XLA_FLAGS"] = current
    return current


def fit_model_parallel(n_devices: int, model_parallel: int) -> Tuple[int, int]:
    """(data, model) for an ``n_devices`` mesh, degrading the requested
    model-parallel degree by halving until it divides — the same fallback
    the elastic-restart path applies, shared so every mesh builder agrees.
    Always returns a valid factorization (model_parallel >= 1 divides
    n_devices, data * model == n_devices)."""
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    model_parallel = max(1, min(model_parallel, n_devices))
    while model_parallel > 1 and n_devices % model_parallel != 0:
        model_parallel //= 2
    return n_devices // model_parallel, model_parallel


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model) — the 'pod' axis
    is pure DP across pods (cross-pod traffic = one gradient reduction)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host has, as a (data, model) mesh — for smoke tests,
    examples and the virtual-device CI.  ``model_parallel`` requests a
    tensor-parallel axis; it degrades by halving until it divides the
    host's device count (1 CPU -> always (1, 1))."""
    data, model = fit_model_parallel(len(jax.devices()), model_parallel)
    return _auto_mesh((data, model), ("data", "model"))


def make_elastic_mesh(n_devices: int, model_parallel: int = 16):
    """Rebuild a (data, model) mesh from a surviving device count — the
    elastic-restart path: after node loss, data parallelism shrinks while
    model parallelism (intra-replica) is preserved when it still divides.
    ``n_devices`` may be a strict subset of the host's devices (the dead
    nodes' devices are simply not in the mesh)."""
    data, model = fit_model_parallel(n_devices, model_parallel)
    return _auto_mesh(
        (data, model), ("data", "model"), devices=jax.devices()[:n_devices]
    )


def make_mesh_shape(shape: Tuple[int, int], *, devices: Optional[list] = None):
    """An explicit (data, model) mesh over the first prod(shape) devices —
    the differential suite builds every shape of its sweep this way on the
    same 8-virtual-device backend."""
    n = shape[0] * shape[1]
    devices = (devices or jax.devices())[:n]
    if len(devices) < n:
        raise ValueError(
            f"mesh shape {shape} needs {n} devices, have {len(devices)}"
        )
    return _auto_mesh(shape, ("data", "model"), devices=devices)
