"""Mesh + shard_map wrappers for the batch engine.

The reference has no cross-process parallelism — docs are independent, so the
TPU-native scaling story (SURVEY.md §2 parallelism table) is: shard the *doc
batch* axis across the device mesh with ``shard_map``; ICI collectives are
used for global metrics, not for integration itself (no cross-doc
communication exists to translate).

One axis, ``docs``: the data-parallel axis — every [B, ...] array is sharded
on its leading dim.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map_impl
from jax.sharding import Mesh, PartitionSpec as P

from ..obs.prof import profiled
from ..ops import kernels

logger = logging.getLogger("yjs_tpu.parallel")


def shard_map(f, mesh, in_specs, out_specs):
    # VMA checking is on by default; the kernels create unvarying
    # intermediates inside the mapped fn, so disable it
    return _shard_map_impl(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def doc_mesh(
    n_devices: int | None = None, axis: str = "docs", backend: str | None = None
) -> Mesh:
    """A 1-D mesh over the doc-batch axis.

    ``backend='cpu'`` builds the virtual host mesh (with
    ``--xla_force_host_platform_device_count=N``) even when a real
    accelerator is the default platform — the multi-chip dry-run path.
    """
    devs = jax.devices(backend) if backend else jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"requested {n_devices} devices, backend has {len(devs)}"
            )
        devs = devs[:n_devices]
    import numpy as np

    return Mesh(np.array(devs), (axis,))


def shard_meshes(
    n_shards: int,
    axis: str = "docs",
    backend: str | None = None,
    devices_per_shard: int | None = None,
) -> list[Mesh | None]:
    """Partition the device list into per-shard 1-D doc meshes — the
    fleet's device-placement map (ISSUE 6): shard ``k`` of a
    :class:`yjs_tpu.fleet.FleetRouter` runs its engine over mesh ``k``,
    so the fleet spans the whole pod while each shard's collectives stay
    inside its own device group.

    Devices are dealt out contiguously (ICI neighbors stay together on
    real TPU topologies).  When the backend has fewer devices than
    shards, every entry is ``None`` — the fleet then runs unmeshed on
    the default device, which is the correct degraded mode for laptops
    and single-chip hosts.
    """
    if n_shards <= 0:
        raise ValueError(f"n_shards must be positive, got {n_shards}")
    devs = jax.devices(backend) if backend else jax.devices()
    if devices_per_shard is None:
        devices_per_shard = len(devs) // n_shards
    if devices_per_shard < 1 or len(devs) < n_shards * devices_per_shard:
        logger.warning(
            "%d shards asked for %d device(s) each and the %s backend has "
            "%d: every shard runs unmeshed on the default device",
            n_shards, max(1, devices_per_shard), devs[0].platform, len(devs),
        )
        return [None] * n_shards
    import numpy as np

    return [
        Mesh(
            np.array(devs[k * devices_per_shard : (k + 1) * devices_per_shard]),
            (axis,),
        )
        for k in range(n_shards)
    ]


def sharded_apply_plan(mesh: Mesh, axis: str, k_dn: int, k_sp: int,
                       k_h: int, k_d: int):
    """The bulk-apply flush sharded over the doc axis: each shard scatters
    its own lanes block into its dyn shard locally (docs are independent —
    no cross-shard communication except the psum'd progress counters).

    lanes: [n_shards, 4*B_local + k_dn + 2*k_sp + 2*k_h + k_d] i32,
    sharded on axis 0; dyn arrays sharded on their doc axis.
    """
    spec = P(axis)

    def local_apply(dyn, lanes):
        lanes1 = lanes[0].astype(jnp.int32)  # int16 lanes widen on device
        b_loc = dyn[0].shape[0]
        out = kernels.apply_lanes(dyn, lanes1, k_dn, k_sp, k_h, k_d)
        integrated = jnp.sum(lanes1[: 2 * b_loc])  # dense + sparse counts
        deleted = jnp.sum(lanes1[3 * b_loc : 4 * b_loc])
        metrics = {
            "integrated": lax.psum(integrated, axis),
            "deleted": lax.psum(deleted, axis),
        }
        return out, metrics

    sharded = shard_map(
        local_apply,
        mesh=mesh,
        in_specs=((spec, spec, spec), spec),
        out_specs=((spec, spec, spec), P()),
    )
    return profiled("sharded_apply_plan")(
        jax.jit(sharded, donate_argnums=(0,))
    )


def sharded_load_rows(mesh: Mesh, axis: str):
    """The row form of the bulk apply (``kernels.apply_plan2_rows``)
    sharded over the doc axis: each shard writes its own
    ``[rows_loc, w]`` block into its dyn shard locally, nothing
    replicated, nothing gathered.

    idx: [n_shards * rows_loc] slots local to their shard (a spare row:
    the shard's doc count, dropped); the blocks [n_shards * rows_loc, w];
    sums: [n_shards, 3] the links, tombstones and heads each shard's
    block holds, the first two psum'd into the progress counters as the
    lanes' counts are.
    All sharded on axis 0; one jitted function for every block shape."""
    spec = P(axis)

    def local_apply_rows(dyn, idx, new_right, new_deleted, new_starts, sums):
        out = kernels.load_rows(dyn, idx, new_right, new_deleted, new_starts)
        metrics = {
            "integrated": lax.psum(sums[0, 0], axis),
            "deleted": lax.psum(sums[0, 1], axis),
        }
        return out, metrics

    sharded = shard_map(
        local_apply_rows,
        mesh=mesh,
        in_specs=((spec, spec, spec), spec, spec, spec, spec, spec),
        out_specs=((spec, spec, spec), P()),
    )
    return profiled("sharded_load_rows")(
        jax.jit(sharded, donate_argnums=(0,))
    )
