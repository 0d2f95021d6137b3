"""Hybrid-parallel topology: axes → named device mesh.

Reference (SURVEY.md §2.6): `CommunicateTopology`/`HybridCommunicateGroup`
(python/paddle/distributed/fleet/base/topology.py) build the dp×pp×sharding×
mp(×sep) rank grid and create one NCCL ProcessGroup per axis.

TPU-native: the grid IS a `jax.sharding.Mesh` with named axes; "groups" are
mesh axes, and collectives ride ICI because the mesh is laid out over the
physical torus by `mesh_utils.create_device_mesh`. One mesh, all axes — GSPMD
inserts the per-axis collectives the reference issues by hand.

Axis order follows the reference ("dp", "pp", "sharding", "sep", "mp"):
outer axes get DCN-ish placement, inner axes (mp/sep) stay on the
fastest ICI links — same intent as Paddle putting mp innermost on NVLink.
An optional "ep" (expert) axis is carved out of dp×sharding for MoE.
"""

import collections
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER = ("dp", "pp", "sharding", "sep", "fsdp", "mp")

#: The sanctioned mesh-axis names, mapped to the degree the multichip
#: dryrun validates (MULTICHIP_r0x leg(16): {dp: 2, pp: 2, sharding: 2,
#: mp: 2} with loss invariance) — None for axes with no pinned degree
#: (`sep` runs degree 1 in the dryrun, `ep` is carved out of
#: dp×sharding per deployment, `g` is the eager collective veneer's
#: private 1-D group axis). This registry is what the `collective-axis`
#: and `pspec-axis` lint rules (paddle_tpu/analysis/rules.py,
#: docs/ANALYSIS.md) pin axis-name literals against: a typo'd or
#: unregistered axis is a lint finding at author time instead of a
#: trace error on a v5p mesh. The degrees feed the pspec-axis
#: sharded-dim divisibility check where tensor sizes are statically
#: known. Registering a new axis here is the one-line gate for
#: introducing it anywhere in the package.
KNOWN_AXES = {
    "dp": 2,
    "pp": 2,
    "sharding": 2,
    "sep": None,
    # fsdp: the serving engine's weight-sharding axis (ServingLayout
    # splits stacked per-layer weights on L over it; mp stays the
    # head/ffn axis). No pinned degree — the serving parity matrix runs
    # it at 1 on CPU and deployments pick L-divisible degrees.
    "fsdp": None,
    "mp": 2,
    "ep": None,
    "g": None,
}


def build_mesh(axis_dims: Dict[str, int], devices=None) -> Mesh:
    """Build a named Mesh from {axis: degree}; degrees must multiply to #devices
    (axes with degree 1 are kept so sharding specs can always name them)."""
    devices = list(devices if devices is not None else jax.devices())
    names = [a for a in AXIS_ORDER if a in axis_dims]
    extra = [a for a in axis_dims if a not in AXIS_ORDER]
    names += extra
    dims = [int(axis_dims[a]) for a in names]
    total = int(np.prod(dims)) if dims else 1
    if total != len(devices):
        raise ValueError(
            f"mesh dims {dict(zip(names, dims))} multiply to {total}, "
            f"but {len(devices)} devices are available")
    from jax.experimental import mesh_utils
    dev_array = mesh_utils.create_device_mesh(dims, devices=devices)
    return Mesh(dev_array, axis_names=tuple(names))


class CommunicateTopology:
    """Rank-grid arithmetic (reference parity: fleet/base/topology.py)."""

    def __init__(self, hybrid_group_names: Sequence[str], dims: Sequence[int]):
        self._names = list(hybrid_group_names)
        self._dims = [int(d) for d in dims]
        self._shape = tuple(self._dims)
        self._world = int(np.prod(self._dims)) if self._dims else 1

    def get_hybrid_group_names(self):
        return list(self._names)

    def get_dim(self, axis_name):
        return self._dims[self._names.index(axis_name)]

    def world_size(self):
        return self._world

    def get_rank(self, **kw):
        coord = [kw[n] for n in self._names]
        return int(np.ravel_multi_index(coord, self._shape))

    def get_coord(self, rank):
        return tuple(int(c) for c in np.unravel_index(rank, self._shape))

    def get_axis_list(self, axis_name, index):
        """All ranks whose coord on `axis_name` equals `index`."""
        ax = self._names.index(axis_name)
        out = []
        for r in range(self._world):
            if self.get_coord(r)[ax] == index:
                out.append(r)
        return out

    def get_comm_list(self, axis_name):
        """List of rank-groups that communicate along `axis_name`."""
        ax = self._names.index(axis_name)
        groups = collections.defaultdict(list)
        for r in range(self._world):
            coord = list(self.get_coord(r))
            coord[ax] = -1
            groups[tuple(coord)].append(r)
        return [sorted(v) for _, v in sorted(groups.items())]


class HybridCommunicateGroup:
    """Builds the global mesh and exposes per-axis degree/rank queries.

    In the reference each axis materializes a ProcessGroupNCCL; here the mesh
    axis name is the group handle — pass `hcg.mesh` + axis names into
    shardings/shard_map and XLA emits the collectives.
    """

    def __init__(self, topology: Optional[CommunicateTopology] = None,
                 strategy=None, devices=None):
        if topology is None:
            cfg = (strategy.hybrid_configs if strategy is not None else {})
            n_dev = len(devices) if devices is not None else jax.device_count()
            dp = cfg.get("dp_degree", 1)
            mp = cfg.get("mp_degree", 1)
            pp = cfg.get("pp_degree", 1)
            sh = cfg.get("sharding_degree", 1)
            sep = cfg.get("sep_degree", 1)
            known = mp * pp * sh * sep
            if dp in (0, -1, None):
                dp = n_dev // known
            topology = CommunicateTopology(
                ["dp", "pp", "sharding", "sep", "mp"], [dp, pp, sh, sep, mp])
        self._topo = topology
        dims = {n: topology.get_dim(n) for n in topology.get_hybrid_group_names()}
        self.mesh = build_mesh(dims, devices=devices)
        self.global_rank = jax.process_index()

    # -- reference accessors -------------------------------------------------

    @property
    def topology(self):
        return self._topo

    def _dim(self, name):
        try:
            return self._topo.get_dim(name)
        except ValueError:
            return 1

    def get_parallel_mode(self):
        if self._dim("pp") > 1:
            return "pipeline"
        if self._dim("sharding") > 1:
            return "sharding"
        if self._dim("mp") > 1:
            return "tensor"
        return "data"

    def get_data_parallel_world_size(self):
        return self._dim("dp")

    def get_model_parallel_world_size(self):
        return self._dim("mp")

    def get_pipe_parallel_world_size(self):
        return self._dim("pp")

    def get_sharding_parallel_world_size(self):
        return self._dim("sharding")

    def get_sep_parallel_world_size(self):
        return self._dim("sep")

    # ranks are meaningful per-process in multi-host; single-process SPMD
    # places all coords in one program, so these report the process's coord.
    def _coord(self):
        return self._topo.get_coord(self.global_rank % self._topo.world_size())

    def get_data_parallel_rank(self):
        return self._coord()[self._topo.get_hybrid_group_names().index("dp")]

    def get_model_parallel_rank(self):
        return self._coord()[self._topo.get_hybrid_group_names().index("mp")]

    def get_stage_id(self):
        return self._coord()[self._topo.get_hybrid_group_names().index("pp")]

    def get_sharding_parallel_rank(self):
        return self._coord()[self._topo.get_hybrid_group_names().index("sharding")]

    # -- mesh views ----------------------------------------------------------

    def axis_size(self, name):
        return self._dim(name)

    def dp_axis(self):
        return "dp"

    def mp_axis(self):
        return "mp"

    def pp_axis(self):
        return "pp"

    def sharding_axis(self):
        return "sharding"


_HCG: List[Optional[HybridCommunicateGroup]] = [None]


def set_hybrid_communicate_group(hcg: HybridCommunicateGroup):
    _HCG[0] = hcg


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _HCG[0]
