"""Device mesh construction (SPMD over ICI) and the PartitionSpec mint.

Replaces all four reference communication backends (SURVEY.md §2.16/§5):
NCCL collective ops (operators/nccl_op.cc), the C++ socket pserver
(paddle/pserver), the Go pserver/master (go/), and gRPC send/recv
(operators/detail) — data/model parallelism become sharding annotations over a
`jax.sharding.Mesh`; XLA emits all-reduce/all-gather/reduce-scatter over ICI.

Axis names:
  dp — data parallel (batch axis)
  mp — model/tensor parallel (hidden/vocab axes)
  sp — sequence parallel (long-context time axis)
  pp — pipeline stages
  dcn* — a "dcn" prefix marks an axis as crossing the data-center
         network instead of ICI (multi-slice meshes); the sharding
         analyzer prices its collectives at DCN bandwidth and PTV021
         flags inner-step collectives that cross it

This module is the ONLY place in `paddle_tpu/parallel/` and beside it
allowed to construct `PartitionSpec` literals (enforced by
tools/repo_lint.py): every other module derives specs through
`pspec`/`named`/`replicated`, so the sharding analyzer can trust that
whatever plan it is handed was minted by rules, not ad-hoc tuples.

A leaf of the package (it imports nothing of it): emitters
(ops/), the partitioner (parallel/) and the sharding analyzer
(analysis/) all read a mesh's axis sizes and take a spec apart with the
helpers at the end (`spec_of`, `entry_axes`, `spec_axes`, `spec_divisor`).
"""

from __future__ import annotations

from typing import Dict, Optional


def axis_size(mesh, name: str, default: int = 1) -> int:
    """Size of mesh axis `name` (`default` when the mesh has no such
    axis) — the one place for the name→size lookup."""
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, default)


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """{axis name: size} for every axis of `mesh`."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dcn_axes(mesh_or_axes) -> tuple:
    """Axis names that cross DCN rather than ICI, by the naming
    convention (a ``dcn`` prefix): hybrid multi-slice meshes name their
    slow axis ``dcn``/``dcn_dp``/... so both the executor and the
    static comm analyzer agree on which links a collective rides."""
    names = getattr(mesh_or_axes, "axis_names", mesh_or_axes)
    return tuple(n for n in names if str(n).startswith("dcn"))


def pspec(*entries):
    """The PartitionSpec mint: one constructor site for all of
    parallel/ (trailing Nones are harmless; jax treats missing and None
    entries identically)."""
    from jax.sharding import PartitionSpec

    return PartitionSpec(*entries)


def named(mesh, *entries):
    """NamedSharding over `mesh` with spec entries `entries`."""
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, pspec(*entries))


def replicated(mesh):
    """Fully-replicated NamedSharding over `mesh`."""
    return named(mesh)


def make_mesh(axes: Optional[Dict[str, int]] = None, devices=None):
    """Build a Mesh. `axes` maps axis name → size; total must divide the
    device count. Default: pure DP over all devices."""
    import numpy as np
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {"dp": len(devices)}
    names = list(axes.keys())
    sizes = [int(axes[n]) for n in names]
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(
            f"mesh {axes} needs {total} devices, have {len(devices)}")
    arr = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(arr, axis_names=names)


def make_hybrid_mesh(ici_axes: Dict[str, int],
                     dcn_axes_map: Dict[str, int], devices=None):
    """Build a multi-slice Mesh: outer `dcn*` axes across slices, inner
    axes within each slice's ICI domain (the create_hybrid_device_mesh
    shape from t5x/maxtext).

    `dcn_axes_map` names MUST carry the ``dcn`` prefix — that prefix is
    the contract by which `dcn_axes`, PTV021, `comm_report`, and the
    ICI-reduce-scatter → DCN-all-reduce → ICI-all-gather decomposition
    recognize slow links; an unprefixed slice axis would silently be
    priced at ICI bandwidth.

    On real multi-slice TPU, devices are grouped by their
    ``slice_index`` attribute so the outer mesh dims walk slices.  On
    CPU/simulated-DCN there are no slice indices: devices are split
    into `num_slices` contiguous chunks, so a 2-slice run over 8
    virtual devices models devices 0-3 as slice 0 and 4-7 as slice
    1."""
    import numpy as np
    import jax
    from jax.sharding import Mesh

    for name in dcn_axes_map:
        if not str(name).startswith("dcn"):
            raise ValueError(
                f"hybrid mesh slice axis {name!r} must carry the 'dcn' "
                f"prefix (the analyzer's link-class convention)")
    devices = list(devices if devices is not None else jax.devices())
    num_slices = int(np.prod(list(dcn_axes_map.values()) or [1]))
    per_slice = int(np.prod(list(ici_axes.values()) or [1]))
    total = num_slices * per_slice
    if total > len(devices):
        raise ValueError(
            f"hybrid mesh {dcn_axes_map} x {ici_axes} needs {total} "
            f"devices, have {len(devices)}")
    devices = devices[:total]
    names = list(dcn_axes_map.keys()) + list(ici_axes.keys())
    sizes = ([int(dcn_axes_map[n]) for n in dcn_axes_map]
             + [int(ici_axes[n]) for n in ici_axes])
    if all(getattr(d, "slice_index", None) is not None for d in devices) \
            and len({d.slice_index for d in devices}) == num_slices:
        # real multi-slice: group by physical slice so the outer (dcn)
        # mesh dims walk slices and the inner dims stay intra-slice ICI
        devices = sorted(devices, key=lambda d: (d.slice_index, d.id))
    # else simulated DCN: contiguous chunks stand in for slices
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, axis_names=names)


# ---------------------------------------------------------------------------
# taking a spec apart


def spec_of(sharding, ndim: Optional[int] = None) -> tuple:
    """Positional spec tuple from a NamedSharding / PartitionSpec /
    tuple, padded with None to `ndim` when given."""
    if sharding is None:
        entries: tuple = ()
    else:
        spec = getattr(sharding, "spec", sharding)
        try:
            entries = tuple(spec)
        except TypeError:
            entries = ()
    out = []
    for e in entries:
        if isinstance(e, (tuple, list)):
            e = tuple(a for a in e if a) or None
            if e is not None and len(e) == 1:
                e = e[0]
        out.append(e if e else None)
    if ndim is not None:
        out = (out + [None] * ndim)[:ndim]
    return tuple(out)


def entry_axes(entry) -> tuple:
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


def spec_axes(spec) -> tuple:
    """Flat mesh-axis names a spec shards over, in dim order."""
    out = []
    for e in spec or ():
        out.extend(entry_axes(e))
    return tuple(out)


def spec_divisor(spec, axis_sizes: Dict[str, int]) -> int:
    d = 1
    for a in spec_axes(spec):
        d *= int(axis_sizes.get(a, 1))
    return max(d, 1)
