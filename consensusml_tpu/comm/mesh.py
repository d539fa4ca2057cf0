"""Device-mesh construction for worker topologies.

Replaces the reference's process-group / communicator bootstrap (SURVEY.md
L1: NCCL rendezvous; file:line unavailable — mount empty). In JAX there is
no rendezvous: "N workers" is N devices in a named
:class:`jax.sharding.Mesh` whose axis names are the topology's gossip axes,
so every ``ppermute`` in the gossip step maps onto ICI neighbor links.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from consensusml_tpu.topology import Topology

__all__ = ["WorkerMesh", "local_device_mesh", "slice_major_devices"]


def slice_major_devices(devices: Sequence[jax.Device] | None = None) -> list[jax.Device]:
    """Order devices slice-major: all of slice 0, then slice 1, ...

    For :class:`~consensusml_tpu.topology.HierarchicalTopology` this is
    the layout that makes the topology's axis 0 ("slices") cross slice
    boundaries — its 1-in-K outer-ring ppermutes ride DCN while the
    per-round inner-ring ppermutes stay on ICI. The sort is stable and
    keys ONLY on ``slice_index``, so devices without one (CPU,
    single-slice pods) keep their original order — safe to call
    unconditionally.
    """
    devices = list(jax.devices() if devices is None else devices)
    return sorted(devices, key=lambda d: getattr(d, "slice_index", 0) or 0)


def local_device_mesh(n: int, platform: str | None = None) -> list[jax.Device]:
    """Return ``n`` local devices, with a helpful error for CPU simulation.

    For multi-worker tests on a dev box: set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the first
    jax import, then request ``platform="cpu"`` here (or pin the default
    with ``JAX_PLATFORMS=cpu`` / ``jax.config.update("jax_platforms",
    "cpu")``).
    """
    devices = jax.devices(platform) if platform else jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for this topology but only {len(devices)} are "
            f"visible ({[d.platform for d in devices[:3]]}...). For CPU "
            "simulation set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            'importing jax and pass platform="cpu" (or '
            'jax.config.update("jax_platforms", "cpu") after import), or use '
            "the simulated backend (consensusml_tpu.comm.simulated) which "
            "runs any world size on one device."
        )
    return list(devices[:n])


@dataclasses.dataclass(frozen=True)
class WorkerMesh:
    """A topology bound to a concrete device mesh.

    Global (host-view) arrays carry ``len(mesh_shape)`` leading worker axes
    — e.g. ``(W, ...)`` for a ring, ``(R, C, ...)`` for a torus — sharded
    one-slice-per-device via :meth:`worker_spec`. Inside ``shard_map`` each
    worker sees its slice with singleton leading axes.

    ``model_axes`` generalizes a worker from one device to a SUBMESH: the
    mesh becomes ``(*topology.mesh_shape, *model_axis_sizes)``. Gossip
    collectives stay manual over the worker axes (``shard_map``
    partial-manual mode) while the model axes remain in XLA *auto*
    sharding mode — annotate params with
    :mod:`consensusml_tpu.parallel.sharding` rules and the compiler
    inserts the intra-worker tensor-parallel collectives. This is how the
    Llama-2-7B torus config runs full-weights on a pod: 4x4 workers x
    tp-submesh each, something the reference's one-process-per-GPU design
    cannot express (SURVEY.md §2: no TP/PP evidence in the reference).
    """

    topology: Topology
    mesh: Mesh
    model_axes: tuple[tuple[str, int], ...] = ()
    manual_model_axes: tuple[str, ...] = ()

    @classmethod
    def create(
        cls,
        topology: Topology,
        devices: Sequence[jax.Device] | None = None,
        platform: str | None = None,
        model_axes: Sequence[tuple[str, int]] = (),
        manual_model_axes: Sequence[str] = (),
    ) -> "WorkerMesh":
        """``manual_model_axes`` marks model axes whose collectives the
        per-worker computation writes ITSELF (``shard_map`` manual mode)
        rather than leaving to XLA's auto sharding — pipeline parallelism
        needs this: ``pipeline_apply``'s stage-to-stage ``ppermute`` is a
        hand-written collective over the ``pp`` axis, unlike TP whose
        psums XLA derives from sharding annotations."""
        model_axes = tuple((str(n), int(s)) for n, s in model_axes)
        manual_model_axes = tuple(str(n) for n in manual_model_axes)
        if overlap := {n for n, _ in model_axes} & set(topology.axis_names):
            raise ValueError(f"model axes {sorted(overlap)} collide with worker axes")
        if missing := set(manual_model_axes) - {n for n, _ in model_axes}:
            raise ValueError(
                f"manual_model_axes {sorted(missing)} are not model axes"
            )
        per_worker = int(np.prod([s for _, s in model_axes])) if model_axes else 1
        need = topology.world_size * per_worker
        if devices is None:
            devices = local_device_mesh(need, platform)
        if len(devices) != need:
            raise ValueError(
                f"topology wants {topology.world_size} workers x {per_worker} "
                f"devices/worker = {need} devices, got {len(devices)}"
            )
        shape = (*topology.mesh_shape, *(s for _, s in model_axes))
        names = (*topology.axis_names, *(n for n, _ in model_axes))
        dev_array = np.asarray(devices, dtype=object).reshape(shape)
        return cls(
            topology=topology,
            mesh=Mesh(dev_array, names),
            model_axes=model_axes,
            manual_model_axes=manual_model_axes,
        )

    @property
    def axis_names(self) -> tuple[str, ...]:
        return self.topology.axis_names

    def worker_devices(self) -> list[jax.Device]:
        """One representative device per worker rank (row-major over the
        worker axes; the first device of each worker's model submesh) —
        the rank -> device map the link prober (obs.links) times its
        edge transfers across."""
        return list(
            np.asarray(self.mesh.devices, dtype=object).reshape(
                self.topology.world_size, -1
            )[:, 0]
        )

    def manual_axes(self) -> frozenset[str] | None:
        """Axes ``shard_map`` should be manual over: worker axes plus any
        manual model axes (e.g. ``pp``) when a model submesh exists
        (partial-manual), else None (fully manual)."""
        if not self.model_axes:
            return None
        return frozenset(self.axis_names) | frozenset(self.manual_model_axes)

    def worker_spec(self) -> PartitionSpec:
        """PartitionSpec sharding the leading worker axes over the mesh."""
        return PartitionSpec(*self.axis_names)

    def replicated_spec(self) -> PartitionSpec:
        return PartitionSpec()

    def worker_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.worker_spec())

    def stacked_sharding(self) -> NamedSharding:
        """Sharding for FLAT-stacked arrays ``(W, ...)``: the single leading
        axis is split over the WORKER mesh axes (row-major), so a later
        reshape to ``mesh_shape`` leading axes is layout-preserving.
        Trailing dims are replicated (over any model axes too) — use
        :meth:`stacked_shardings` with rules to also split model dims."""
        return NamedSharding(self.mesh, PartitionSpec(self.axis_names))

    def stacked_shardings(self, tree, rules=None):
        """Per-leaf NamedSharding tree for flat-stacked arrays: leading axis
        over the worker axes, trailing dims per the model-sharding
        ``rules`` (see :mod:`consensusml_tpu.parallel.sharding`)."""
        from consensusml_tpu.parallel import sharding as _sharding

        return _sharding.stacked_shardings(tree, self.mesh, self.axis_names, rules)

    def shard_stacked(self, tree, rules=None, shardings=None):
        """Place a flat-stacked pytree onto the mesh.

        Single-process: plain ``device_put``. Multi-controller
        (``jax.process_count() > 1``): ``device_put`` cannot target
        non-addressable devices, so each process contributes its
        addressable shards via ``make_array_from_callback`` — the input
        tree must hold the same GLOBAL host values on every process
        (true for seeded init and the keyed data loaders). Pass a
        precomputed ``shardings`` tree (from :meth:`stacked_shardings`)
        to skip recomputation on hot paths.
        """
        import jax as _jax
        import numpy as _np

        if shardings is None:
            shardings = self.stacked_shardings(tree, rules)

        def _placed(x, sharding) -> bool:
            # a leaf the device prefetcher (or a previous shard_stacked)
            # already committed with the target sharding is used AS IS —
            # re-putting it would be the "second transfer" the overlapped
            # feed exists to avoid
            return (
                isinstance(x, _jax.Array)
                and getattr(x, "sharding", None) == sharding
            )

        if _jax.process_count() == 1:
            return _jax.tree.map(
                lambda x, s: x if _placed(x, s) else _jax.device_put(x, s),
                tree,
                shardings,
            )

        def put(x, sharding):
            if _placed(x, sharding):
                return x
            if hasattr(x, "dtype") and _jax.dtypes.issubdtype(
                x.dtype, _jax.dtypes.prng_key
            ):
                # typed PRNG keys can't cross the numpy boundary: ship the
                # raw key data (extra trailing dim, replicated) and re-wrap
                impl = _jax.random.key_impl(x)
                raw = _np.asarray(_jax.device_get(_jax.random.key_data(x)))
                rsharding = NamedSharding(
                    sharding.mesh, PartitionSpec(*sharding.spec, None)
                )
                garr = _jax.make_array_from_callback(
                    raw.shape, rsharding, lambda idx: raw[idx]
                )
                return _jax.random.wrap_key_data(garr, impl=impl)
            host = _np.asarray(x)
            return _jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx]
            )

        return _jax.tree.map(put, tree, shardings)

    def stack_shape(self) -> tuple[int, ...]:
        """Leading axes a global stacked array must carry."""
        return self.topology.mesh_shape
