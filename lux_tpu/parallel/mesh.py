"""Device mesh helpers.

The TPU replacement for the reference's machine model + mapper
(core/lux_mapper.cc): where LuxMapper discovers GPUs/framebuffers and slices
index launches one point per GPU round-robin across nodes
(lux_mapper.cc:102-140), we declare a 1-D `jax.sharding.Mesh` over all chips
and let GSPMD/shard_map place one graph part per chip.  Memory placement
(the FB vs zero-copy tags, core/graph.h:33-34) needs no analog: sharded
arrays live in HBM; the all-gathered state is XLA-managed.

Axis naming convention:
  * ``parts`` — the graph partition axis (one contiguous vertex range per
    chip; the sequence/context-parallel analog, SURVEY.md §2.5).
  * ``feat``  — optional second axis for feature-dimension sharding of
    wide vertex states (CF latent vectors; tensor-parallel analog) —
    see parallel/feat.py.
"""
from __future__ import annotations

import logging
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("lux_tpu")

PARTS_AXIS = "parts"
FEAT_AXIS = "feat"


def make_mesh(num_parts: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over ``num_parts`` devices (default: all)."""
    if devices is None:
        devices = jax.devices()
    if num_parts is None:
        num_parts = len(devices)
    assert len(devices) >= num_parts, (len(devices), num_parts)
    return Mesh(np.asarray(devices[:num_parts]), (PARTS_AXIS,))


def make_mesh_for_parts(num_parts: int, devices: Optional[Sequence] = None) -> Mesh:
    """Mesh sized for ``num_parts`` graph parts on however many devices
    exist: if parts exceed devices, pick the largest mesh size that
    divides the part count, leaving k = parts/size parts RESIDENT per
    device — the analog of the reference mapper slicing up to
    MAX_NUM_PARTS=64 parts across whatever processors exist
    (core/graph.h:31, core/lux_mapper.cc:102-122)."""
    if devices is None:
        devices = jax.devices()
    d = min(len(devices), num_parts)
    while num_parts % d:
        d -= 1
    if num_parts > len(devices) and d < len(devices):
        log.warning(
            "num_parts=%d shares no divisor with the %d available devices"
            " above %d: running a %d-device mesh (%d idle). Pick -ng as a"
            " multiple of the device count to use every chip.",
            num_parts, len(devices), d, d, len(devices) - d,
        )
    return make_mesh(d, devices)


def parts_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (part) axis; replicate the rest."""
    return NamedSharding(mesh, P(PARTS_AXIS))


def shard_stacked(mesh: Mesh, tree):
    """Place a pytree of stacked (P, ...) arrays with axis 0 on the mesh.

    Host (NumPy) leaves go straight to their devices, each device its
    own k = P / D parts: nothing is staged whole on one device first.  A
    leaf already laid out this way is returned as it is (no copy)."""
    sh = parts_sharding(mesh)

    def put(a):
        if isinstance(a, jax.Array) and a.sharding.is_equivalent_to(
                sh, a.ndim):
            return a
        return jax.device_put(a, sh)

    return jax.tree.map(put, tree)


def parts_mesh(tree) -> Optional[Mesh]:
    """The mesh of more than one device whose parts axis the stacked
    tree's arrays are laid over, else None (host arrays, one device).
    Reads the first leaf's sharding: no device sync."""
    leaves = jax.tree.leaves(tree)
    sh = getattr(leaves[0], "sharding", None) if leaves else None
    if (isinstance(sh, NamedSharding) and sh.mesh.size > 1
            and sh.spec and sh.spec[0] == PARTS_AXIS):
        return sh.mesh
    return None


def flatten_gather(block):
    """all_gather a (k, V, ...) resident block over the parts axis and
    flatten to the (P*V, ...) gathered-coordinate state.  Thin alias of
    ``placement.halo_all_gather`` — the canonical halo-exchange leg
    (parallel/placement.py owns the ordering invariant and the LUX-J3
    audit); kept here for the historical import path."""
    from lux_tpu.parallel.placement import halo_all_gather

    return halo_all_gather(block)


def routed_run_args(mesh, route):
    """Shared tail for routed exchange drivers: device-shard the plan
    arrays over the parts axis and resolve interpret mode.  Returns
    (route_static, sharded_arrays, interpret)."""
    from lux_tpu.engine.pull import _route_interpret

    rs, ra = route
    return rs, shard_stacked(mesh, ra), _route_interpret()
