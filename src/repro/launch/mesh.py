"""Production mesh construction and multi-host launch.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count is locked on first jax init, and
smoke tests must see 1 device while the dry-run sees 512).

Multi-host support (see DESIGN.md §Multi-host topology):

* ``init_multihost`` / ``init_multihost_from_env`` bring a process into a
  ``jax.distributed`` cluster before any other jax use — on CPU they
  select the gloo collectives backend, which is what the localhost
  emulation rig (tests/CI) runs on.
* ``make_fft_mesh(hosts=, local=)`` builds the FFT axis *host-major*:
  device ``H*local + L`` is local device ``L`` of host ``H``, so the
  hierarchical exchange's intra-host groups are contiguous runs along
  the axis.  ``make_pfft3_mesh(hosts=)`` does the same with the host
  dimension riding the ``r`` axis (each host owns ``r/hosts`` contiguous
  mesh rows; ``c``-axis communicators never leave a host).
* ``mesh_host_shape`` recovers ``(hosts, local)`` along a mesh axis —
  from the device ``process_index`` pattern on a real multi-process
  cluster, or from the emulated-host registry that single-process tests
  populate via ``hosts=`` so the hierarchical code paths are exercised
  without multi-process launches.
"""

from __future__ import annotations

import os

import numpy as np

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "make_fft_mesh",
           "make_pfft3_mesh", "mesh_host_shape", "register_emulated_hosts",
           "init_multihost", "init_multihost_from_env"]

# Single-process emulation of host structure: (axis_name, flat device ids)
# -> host count along that axis.  Populated by ``hosts=`` mesh builders
# (and ``register_emulated_hosts``) when there is only one real process;
# consulted by ``mesh_host_shape`` before the process_index derivation.
_EMULATED_HOSTS: dict[tuple[str, tuple[int, ...]], int] = {}


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def _mesh_from_devices(grid, axes):
    """Mesh over an *explicit* device array (host-major orderings must not
    be re-shuffled by ``jax.make_mesh``'s own placement heuristics)."""
    from jax.sharding import Mesh
    return Mesh(np.asarray(grid), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def host_major_devices(devices=None):
    """Visible devices sorted host-major: by (process_index, id)."""
    devices = list(devices if devices is not None else jax.devices())
    return sorted(devices,
                  key=lambda d: (getattr(d, "process_index", 0), d.id))


def register_emulated_hosts(mesh, axis_name: str, hosts: int) -> None:
    """Declare that ``mesh``'s ``axis_name`` axis is ``hosts`` host-major
    groups — the single-process stand-in for ``process_index`` structure,
    used by tests and the elastic rebuild path on forced-device rigs.

    ``hosts=1`` clears any prior declaration: the registry is keyed by
    (axis name, device ids), so the *last builder wins* — building a flat
    mesh over devices that previously carried an emulated hierarchy must
    not inherit it.
    """
    ids = tuple(int(d.id) for d in np.asarray(mesh.devices).flat)
    if int(hosts) <= 1:
        _EMULATED_HOSTS.pop((axis_name, ids), None)
    else:
        _EMULATED_HOSTS[(axis_name, ids)] = int(hosts)


def mesh_host_shape(mesh, axis_name: str = "fft") -> tuple[int, int]:
    """``(hosts, local)`` along ``mesh``'s ``axis_name`` axis.

    Returns ``(1, p)`` — no exploitable hierarchy — unless the axis is
    *host-major*: equal-sized contiguous runs of same-host devices (the
    layout the ``hosts=`` builders produce).  A flat or shuffled layout
    degrades to single-tier treatment rather than raising: the exchange
    still works, it just has no fast-tier grouping to exploit.
    """
    axis_names = tuple(mesh.axis_names)
    if axis_name not in axis_names:
        raise ValueError(f"mesh has no axis {axis_name!r}: {axis_names}")
    p = int(mesh.shape[axis_name])
    ids = tuple(int(d.id) for d in np.asarray(mesh.devices).flat)
    hosts = _EMULATED_HOSTS.get((axis_name, ids))
    if hosts is not None:
        if hosts >= 1 and p % hosts == 0:
            return int(hosts), p // hosts
        return 1, p
    axis_pos = axis_names.index(axis_name)
    along = np.moveaxis(np.asarray(mesh.devices), axis_pos, 0).reshape(p, -1)
    # Host pattern must agree across every communicator of this axis.
    procs = [[getattr(d, "process_index", 0) for d in along[:, j]]
             for j in range(along.shape[1])]
    pattern = procs[0]
    if any(q != pattern for q in procs[1:]):
        return 1, p
    hosts = len(dict.fromkeys(pattern))
    if hosts <= 1 or p % hosts:
        return 1, p
    local = p // hosts
    blocks = [pattern[i * local:(i + 1) * local] for i in range(hosts)]
    if any(len(set(b)) != 1 for b in blocks) \
            or len({b[0] for b in blocks}) != hosts:
        return 1, p
    return hosts, local


def init_multihost(coordinator_address: str, num_processes: int,
                   process_id: int) -> None:
    """Join a ``jax.distributed`` cluster; call before any other jax use.

    On CPU this selects the gloo collectives backend — XLA's default CPU
    collectives cannot cross process boundaries — which is exactly what
    the localhost emulation rig (2 processes x 2 forced devices) runs on
    in CI.  Idempotent per process: a second call is a no-op.
    """
    if getattr(jax.distributed, "global_state", None) is not None \
            and jax.distributed.global_state.client is not None:
        return
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):  # pragma: no cover - non-CPU builds
        pass
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=int(num_processes),
                               process_id=int(process_id))


def init_multihost_from_env() -> bool:
    """``init_multihost`` from ``REPRO_MH_COORD`` / ``REPRO_MH_NPROCS`` /
    ``REPRO_MH_PID`` (the launcher contract of the multihost test rig and
    any external process manager); returns False when unset."""
    coord = os.environ.get("REPRO_MH_COORD")
    if not coord:
        return False
    init_multihost(coord, int(os.environ["REPRO_MH_NPROCS"]),
                   int(os.environ["REPRO_MH_PID"]))
    return True


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ('data', 'model').  Multi-pod: 2 pods
    = 512 chips ('pod', 'data', 'model'); the pod axis carries pure DP so
    only gradient all-reduces cross the (slow) pod interconnect."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist —
    used by tests and the quickstart example."""
    return _make_mesh((data, model), ("data", "model"))


def make_fft_mesh(p: int | None = None, axis_name: str = "fft", *,
                  hosts: int | None = None, local: int | None = None):
    """1-D mesh for the distributed PFFT pipeline (and its tuner).

    ``p`` defaults to every visible device — on a forced-multi-device CPU
    host (``--xla_force_host_platform_device_count=k``) that is the faked
    topology the dist test rig and the microbench ``dist`` sweep run on.
    The axis name is part of the plan's ``topology_digest``, so callers
    who rename it get distinct wisdom keys by construction.

    ``hosts``/``local`` build the axis *host-major* over ``hosts x local``
    devices (either may be derived from the other and the device count):
    on a real ``jax.distributed`` cluster devices are ordered by
    ``(process_index, id)``; in a single process the host structure is
    *emulated* — registered so ``mesh_host_shape`` (and with it the
    hierarchical exchange, the two-tier cost model, and the topology
    digest) treats the mesh as multi-host.  ``hosts=1`` is the flat mesh.
    """
    if hosts is None and local is None:
        if p is None:
            p = jax.device_count()
        mesh = _make_mesh((int(p),), (axis_name,))
        if jax.process_count() == 1:
            register_emulated_hosts(mesh, axis_name, 1)
        return mesh
    devices = host_major_devices()
    if hosts is None:
        total = int(p) if p is not None else len(devices)
        hosts = total // int(local)
    if local is None:
        total = int(p) if p is not None else len(devices)
        local = total // int(hosts)
    hosts, local = int(hosts), int(local)
    p = hosts * local
    if hosts < 1 or local < 1:
        raise ValueError(f"hosts x local must be positive, got {hosts}x{local}")
    if p > len(devices):
        raise ValueError(
            f"host-major mesh needs {hosts}x{local}={p} devices, "
            f"only {len(devices)} visible")
    mesh = _mesh_from_devices(np.asarray(devices[:p]), (axis_name,))
    if jax.process_count() == 1:
        register_emulated_hosts(mesh, axis_name, hosts)
    return mesh


def make_pfft3_mesh(r: int | None = None, c: int | None = None,
                    axis_names: tuple[str, str] = ("fft_r", "fft_c"), *,
                    hosts: int | None = None):
    """2-D ``r x c`` mesh for the pencil-parallel 3-D PFFT.

    Defaults to the most-square factorization of every visible device
    (``r <= c``); passing one of ``r``/``c`` derives the other from the
    device count.  Both axis names enter the plan's ``topology_digest``,
    so a transposed mesh gets distinct wisdom keys by construction.

    ``hosts`` builds the grid host-major with the host dimension riding
    the ``r`` axis: each host owns ``r/hosts`` contiguous mesh rows, so
    every ``c``-axis communicator stays inside one host and only the
    ``r``-axis exchange crosses the slow tier (where the hierarchical
    form applies).  Requires ``hosts | r``.
    """
    if r is None and c is None:
        q = jax.device_count()
        if hosts is not None and int(hosts) > 1:
            # Host-major default: whole hosts stack on the r axis.
            r = int(hosts)
            c = q // r
        else:
            r = 1
            for f in range(int(q ** 0.5), 0, -1):
                if q % f == 0:
                    r = f
                    break
            c = q // r
    elif r is None:
        c = int(c)
        r = jax.device_count() // c
    elif c is None:
        r = int(r)
        c = jax.device_count() // r
    r, c = int(r), int(c)
    if hosts is None:
        mesh = _make_mesh((r, c), tuple(axis_names))
        if jax.process_count() == 1:
            register_emulated_hosts(mesh, axis_names[0], 1)
        return mesh
    hosts = int(hosts)
    if hosts < 1 or r % hosts:
        raise ValueError(
            f"host count must divide the r axis: hosts={hosts}, r={r}")
    devices = host_major_devices()
    if r * c > len(devices):
        raise ValueError(
            f"host-major pencil mesh needs {r}x{c}={r * c} devices, "
            f"only {len(devices)} visible")
    grid = np.asarray(devices[:r * c]).reshape(r, c)
    mesh = _mesh_from_devices(grid, tuple(axis_names))
    if jax.process_count() == 1:
        register_emulated_hosts(mesh, axis_names[0], hosts)
    return mesh
