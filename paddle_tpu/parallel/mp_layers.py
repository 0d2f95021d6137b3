"""Megatron-style tensor-parallel layers, TPU-native.

Reference (SURVEY.md §2.6-TP): `ColumnParallelLinear`, `RowParallelLinear`,
`VocabParallelEmbedding`, `ParallelCrossEntropy` in
python/paddle/distributed/fleet/meta_parallel/parallel_layers/mp_layers.py,
with hand-written identity/allreduce custom autograd ops
(fleet/layers/mpu/mp_ops.py: `_c_identity`, `_c_allreduce`, `_c_split`).

TPU-first design: under GSPMD there is no custom autograd — each layer

* annotates its parameters with a `PartitionSpec` placement hint
  (``Parameter.pspec``, consumed by fleet's train-step builder), and
* places `with_sharding_constraint` hints on activations so XLA's sharding
  propagation reproduces the Megatron comm pattern (identity fwd / allreduce
  bwd for column, allreduce fwd / identity bwd for row) — including the
  backward collectives, automatically, because constraints apply to the
  transposed program too.

Numerics are device-count invariant: on one device every constraint is a
no-op and the layers equal their dense counterparts (tested in
tests/test_mp_layers.py via the 8-device CPU mesh).
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.nn.layer import Layer
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as init
from paddle_tpu.parallel.topology import get_hybrid_communicate_group

MP_AXIS = "mp"


def _active_mesh(axis: str):
    """The hybrid mesh, if one is set and `axis` has degree > 1."""
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        return None
    mesh = hcg.mesh
    if axis in mesh.axis_names and mesh.shape[axis] > 1:
        return mesh
    return None


def constrain(x, spec_for_ndim, axis: str = MP_AXIS):
    """Apply a sharding constraint if a mesh with `axis` is active.

    `spec_for_ndim(ndim) -> PartitionSpec` builds the rank-appropriate spec.

    Dispatch: when an ambient abstract mesh is set (under ``jax.set_mesh`` —
    notably inside a partial-manual ``shard_map`` like the pipeline schedule),
    use a bare PartitionSpec so the constraint applies to the mesh's Auto
    axes; axes the caller has taken Manual are skipped (explicit collectives
    own them there). Otherwise fall back to the hybrid group's concrete mesh.
    """
    from jax.sharding import get_abstract_mesh, AxisType
    am = get_abstract_mesh()
    if not am.empty and axis in am.axis_names:
        types = dict(zip(am.axis_names, am.axis_types))
        if types[axis] == AxisType.Manual or am.shape[axis] <= 1:
            return x
        return jax.lax.with_sharding_constraint(x, spec_for_ndim(x.ndim))
    mesh = _active_mesh(axis)
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for_ndim(x.ndim)))


def _last_dim_spec(axis):
    return lambda nd: P(*([None] * (nd - 1) + [axis]))


def _seq_dim_spec(axis, seq_dim=1):
    def spec(nd):
        dims = [None] * nd
        dims[seq_dim] = axis
        return P(*dims)
    return spec


def _replicated_spec():
    return lambda nd: P(*([None] * nd))


class ColumnParallelLinear(Layer):
    """Linear with the output dim sharded over the mp axis.

    Forward comm: identity (input replicated); backward: allreduce of the
    input grad — both inserted by GSPMD from the weight/activation shardings.
    """

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None, dtype=None, axis: str = MP_AXIS):
        super().__init__()
        w_init = weight_attr if isinstance(weight_attr, init.Initializer) \
            else init.XavierNormal()
        self.weight = self.create_parameter(
            (in_features, out_features), dtype=dtype, default_initializer=w_init)
        self._parameters["weight"].pspec = P(None, axis)
        self._parameters["weight"].is_distributed = True
        if has_bias:
            self.bias = self.create_parameter(
                (out_features,), dtype=dtype, is_bias=True)
            self._parameters["bias"].pspec = P(axis)
            self._parameters["bias"].is_distributed = True
        else:
            self.bias = None
        self.gather_output = gather_output
        self.axis = axis
        self.in_features, self.out_features = in_features, out_features

    def forward(self, x):
        y = F.linear(x, self.weight,
                     self.bias if "bias" in self._parameters else None)
        if self.gather_output:
            return constrain(y, _replicated_spec(), self.axis)
        return constrain(y, _last_dim_spec(self.axis), self.axis)


class RowParallelLinear(Layer):
    """Linear with the input (contracting) dim sharded over the mp axis.

    Forward comm: allreduce of the partial products; backward: identity —
    GSPMD emits the psum because the contraction dim is sharded.
    """

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False, fuse_matmul_bias=False,
                 mp_group=None, name=None, dtype=None, axis: str = MP_AXIS):
        super().__init__()
        w_init = weight_attr if isinstance(weight_attr, init.Initializer) \
            else init.XavierNormal()
        self.weight = self.create_parameter(
            (in_features, out_features), dtype=dtype, default_initializer=w_init)
        self._parameters["weight"].pspec = P(axis, None)
        self._parameters["weight"].is_distributed = True
        if has_bias:
            # bias is added once, after the reduce — replicated
            self.bias = self.create_parameter(
                (out_features,), dtype=dtype, is_bias=True)
        else:
            self.bias = None
        self.input_is_parallel = input_is_parallel
        self.axis = axis
        self.in_features, self.out_features = in_features, out_features

    def _out_spec(self):
        """Output placement after the reduce — SP subclass reduce-scatters."""
        return _replicated_spec()

    def forward(self, x):
        if self.input_is_parallel:
            x = constrain(x, _last_dim_spec(self.axis), self.axis)
        y = jnp.matmul(x, self.weight)
        y = constrain(y, self._out_spec(), self.axis)
        if "bias" in self._parameters and self.bias is not None:
            y = y + self.bias
        return y


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab dim sharded over the mp axis.

    The reference masks out-of-shard ids, looks up locally, then allreduces;
    GSPMD derives the identical pattern from the row-sharded table.
    """

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None, dtype=None, axis: str = MP_AXIS):
        super().__init__()
        w_init = weight_attr if isinstance(weight_attr, init.Initializer) \
            else init.Normal(0.0, 1.0)
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), dtype=dtype,
            default_initializer=w_init)
        self._parameters["weight"].pspec = P(axis, None)
        self._parameters["weight"].is_distributed = True
        self.num_embeddings, self.embedding_dim = num_embeddings, embedding_dim
        self.axis = axis

    def forward(self, x):
        y = F.embedding(x, self.weight)
        return constrain(y, _replicated_spec(), self.axis)


class ParallelCrossEntropy(Layer):
    """Softmax cross-entropy over vocab-sharded logits.

    The reference computes a local max/sum + two allreduces
    (fleet/layers/mpu/mp_ops.py `_c_softmax_with_cross_entropy`); here the
    logits are constrained vocab-sharded and XLA decomposes the logsumexp
    reduction into the same pattern.
    """

    def __init__(self, mp_group=None, name=None, ignore_index=-100,
                 axis: str = MP_AXIS):
        super().__init__()
        self.ignore_index = ignore_index
        self.axis = axis

    def forward(self, logits, labels, soft_label=False, reduction="none"):
        logits = constrain(logits, _last_dim_spec(self.axis), self.axis)
        return F.cross_entropy(logits, labels, soft_label=soft_label,
                               ignore_index=self.ignore_index,
                               reduction=reduction)


# ---- Megatron sequence parallelism (SP over the mp axis) -------------------
# Reference: python/paddle/distributed/fleet/utils/sequence_parallel_utils.py.
# Between TP regions activations are sharded along the sequence dim on the mp
# axis; entering a TP region all-gathers seq, leaving it reduce-scatters.
# Under GSPMD each of these is a sharding constraint.

def scatter(x, axis: str = MP_AXIS, seq_dim: int = 1):
    """ScatterOp parity: replicated → seq-sharded (fwd split, bwd allgather)."""
    return constrain(x, _seq_dim_spec(axis, seq_dim), axis)


def gather(x, axis: str = MP_AXIS, seq_dim: int = 1):
    """GatherOp parity: seq-sharded → replicated."""
    return constrain(x, _replicated_spec(), axis)


class AllGatherOp(Layer):
    """all-gather seq fwd / reduce-scatter bwd (entering a TP region)."""

    def __init__(self, axis: str = MP_AXIS, seq_dim: int = 1):
        super().__init__()
        self.axis, self.seq_dim = axis, seq_dim

    def forward(self, x):
        return gather(x, self.axis, self.seq_dim)


class ReduceScatterOp(Layer):
    """reduce-scatter seq fwd / all-gather bwd (leaving a TP region)."""

    def __init__(self, axis: str = MP_AXIS, seq_dim: int = 1):
        super().__init__()
        self.axis, self.seq_dim = axis, seq_dim

    def forward(self, x):
        return scatter(x, self.axis, self.seq_dim)


class ColumnSequenceParallelLinear(ColumnParallelLinear):
    """Column-parallel linear whose input arrives seq-sharded (SP)."""

    def __init__(self, *args, seq_dim: int = 1, **kwargs):
        kwargs.setdefault("gather_output", False)
        super().__init__(*args, **kwargs)
        self.seq_dim = seq_dim

    def forward(self, x):
        x = constrain(x, _seq_dim_spec(self.axis, self.seq_dim), self.axis)
        return super().forward(x)


class RowSequenceParallelLinear(RowParallelLinear):
    """Row-parallel linear whose output leaves seq-sharded (SP)."""

    def __init__(self, *args, seq_dim: int = 1, **kwargs):
        kwargs.setdefault("input_is_parallel", True)
        super().__init__(*args, **kwargs)
        self.seq_dim = seq_dim

    def _out_spec(self):
        return _seq_dim_spec(self.axis, self.seq_dim)


def mark_as_sequence_parallel_parameter(param):
    """Reference tags SP params (e.g. layernorm inside SP regions) so their
    grads get allreduced over mp; GSPMD derives that from the replicated
    param sharding, so this is a recorded no-op kept for API parity."""
    setattr(param, "sequence_parallel", True)
    return param


def register_sequence_parallel_allreduce_hooks(model, accumulation_steps=1,
                                               fuse_allreduce=False):
    """No-op under GSPMD (grad psum over mp is emitted by the compiler)."""
    return model


# ---- paddle.distributed.split parity ---------------------------------------

def split_layer(size, operation="linear", axis=1, num_partitions=None,
                gather_out=True, weight_attr=None, bias_attr=None):
    """`paddle.distributed.split` parity: build the sharded layer directly.

    operation='linear': axis=0 → RowParallelLinear, axis=1 → ColumnParallel.
    operation='embedding': VocabParallelEmbedding.
    """
    if operation == "embedding":
        return VocabParallelEmbedding(size[0], size[1], weight_attr=weight_attr)
    if operation != "linear":
        raise ValueError(f"unsupported operation {operation!r}")
    in_f, out_f = size
    if axis == 0:
        return RowParallelLinear(in_f, out_f, weight_attr=weight_attr,
                                 has_bias=bias_attr is not False,
                                 input_is_parallel=not gather_out)
    return ColumnParallelLinear(in_f, out_f, weight_attr=weight_attr,
                                has_bias=bias_attr is not False,
                                gather_output=gather_out)
