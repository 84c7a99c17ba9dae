"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's forced
512-device host platform to initialize first.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever devices exist locally, as (data, model) — tests/examples."""
    n = jax.device_count()
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"))


def make_edge_mesh(n_devices: int | None = None, n_edges: int | None = None):
    """1-D datastore mesh over the logical edge axis ("edge",): each device
    hosts a contiguous block of E / n_devices ground edge servers (the
    federation story — a device plays the role of one edge site's local
    store). ``n_devices`` defaults to every local device; it must divide the
    deployment's ``StoreConfig.n_edges`` — pass ``n_edges`` to validate that
    at construction instead of failing later inside the runtime. Simulate a
    fleet on CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

    The device blocks double as *failure domains*: ``AerialDB.fail_device(d)``
    kills exactly device d's block (``distributed.sharding.device_edge_block``),
    and ``StoreConfig.n_failure_domains = n_devices`` makes placement spread
    every shard's replicas across blocks so that loss is survivable."""
    from repro.distributed.sharding import check_edge_partition
    n = jax.device_count() if n_devices is None else n_devices
    if n_edges is not None:
        check_edge_partition(n_edges, n, "the 1-D edge mesh")
    return jax.make_mesh((n,), ("edge",))


def make_fleet_mesh(n_fleet: int, n_edge_per_fleet: int | None = None,
                    n_edges: int | None = None):
    """2-D datastore mesh ("fleet", "edge"): the cross-host generalization of
    ``make_edge_mesh``. The logical edge axis is partitioned over the axis
    *product*, fleet-major — fleet f's devices host the contiguous edge
    blocks ``f * n_edge_per_fleet .. (f+1) * n_edge_per_fleet - 1`` — so each
    host (or host-group) owns one geographically-distinct fleet partition,
    intra-fleet collectives stay on-host ("edge" axis), and only the narrow
    inter-fleet merge crosses hosts ("fleet" axis). ``make_edge_mesh`` is the
    ``n_fleet == 1`` degenerate case of the same contract.

    ``n_edge_per_fleet`` defaults to ``device_count // n_fleet``. Under
    ``jax.distributed`` (one process per fleet partition — see
    ``init_fleet_processes``), the mesh spans every *global* device; jax's
    default device order enumerates processes major-to-minor, so process p's
    local devices form fleet p exactly when each process contributes
    ``n_edge_per_fleet`` devices. Pass ``n_edges`` to validate divisibility
    at construction."""
    from repro.distributed.sharding import check_edge_partition
    if n_fleet < 1:
        raise ValueError(f"n_fleet={n_fleet} must be >= 1.")
    if n_edge_per_fleet is None:
        n_dev = jax.device_count()
        if n_dev % n_fleet:
            raise ValueError(
                f"n_fleet={n_fleet} does not divide the available "
                f"{n_dev} devices; pass n_edge_per_fleet explicitly.")
        n_edge_per_fleet = n_dev // n_fleet
    if n_edges is not None:
        check_edge_partition(n_edges, n_fleet * n_edge_per_fleet,
                             "the (fleet, edge) mesh")
    return jax.make_mesh((n_fleet, n_edge_per_fleet), ("fleet", "edge"))


def init_fleet_processes(coordinator_address: str, num_processes: int,
                         process_id: int) -> None:
    """``jax.distributed.initialize`` wiring for a multi-process fleet
    runtime: one OS process per fleet partition (paper scale: one physical
    host per edge cluster). Call BEFORE any other jax API touches the
    backend. After this, ``jax.device_count()`` is global and
    ``make_fleet_mesh(num_processes)`` lays each process's local devices out
    as one fleet row, so the "edge" axis collectives stay process-local and
    only the "fleet" axis crosses hosts.

    On CPU backends (the simulated-fleet path driven by
    ``benchmarks/fed_worker.py`` / ``benchmarks/multihost_smoke.py``),
    cross-process collectives need the gloo transport, which is selected
    here; real TPU/GPU backends ignore that knob and use their native
    fabric."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
