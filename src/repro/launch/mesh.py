"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (smoke tests see 1 device; only dryrun.py forces 512).
"""
from __future__ import annotations

import jax


def _make(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) single-pod (256 chips) or (2,16,16) two-pod (512 chips).

    Axes: "pod" — cross-pod data parallelism (gradient all-reduce only);
    "data" — in-pod data parallel + FSDP/ZeRO; "model" — tensor/expert
    parallel (highest-bandwidth, innermost axis).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic scaling / tests): same axis-name contract."""
    return _make(shape, axes)


def make_serving_mesh(n_slices: int | None = None, model: int = 1):
    """("data", "model") mesh for the sharded serving gateway.

    ``n_slices`` data-parallel gateway slices (default: as many as the
    device count affords at ``model`` tensor-parallel devices per slice).
    On CPU the device count comes from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — how the
    sharded CI job and tests/test_sharded.py run an 8-way mesh on one
    host.  Factor the result with ``dist.sharding.slice_meshes`` to get
    the per-slice sub-meshes the gateway router schedules over.
    """
    n_dev = jax.device_count()
    if n_slices is None:
        n_slices = max(1, n_dev // model)
    assert n_slices * model <= n_dev, \
        f"serving mesh {n_slices}x{model} exceeds {n_dev} devices"
    return _make((n_slices, model), ("data", "model"))


def make_disagg_meshes(n_prefill: int, n_decode: int, *,
                       prefill_model: int = 1, decode_model: int = 1):
    """Role-partitioned slice meshes for disaggregated prefill/decode.

    Prefill and decode want different partitionings (JetStream's engine
    API makes the same split): prefill slices are few and model-parallel
    (compute-bound chunked folds), decode slices are many lanes
    (memory-bound in-place ticks).  Returns ``(prefill_meshes,
    decode_meshes)`` — per-slice ``("model",)`` sub-meshes over disjoint
    device groups, prefill slices taking the leading devices.  Feed the
    concatenated list to ``shard.build_slices`` and describe the split
    with a ``shard.RolePlan`` (``RolePlan.split(n_prefill, n_decode)``).
    """
    from jax.sharding import Mesh
    import numpy as np
    assert n_prefill >= 1 and n_decode >= 1, \
        "disaggregation needs at least one slice per role"
    need = n_prefill * prefill_model + n_decode * decode_model
    devs = jax.devices()
    assert need <= len(devs), \
        f"disagg mesh needs {need} devices; have {len(devs)}"
    out, k = [], 0
    for n, model in ((n_prefill, prefill_model), (n_decode, decode_model)):
        role = []
        for _ in range(n):
            role.append(Mesh(np.asarray(devs[k:k + model]), ("model",)))
            k += model
        out.append(role)
    return tuple(out)
