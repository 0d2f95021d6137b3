"""Fleet — hybrid-parallel orchestration (≈ paddle.distributed.fleet).

Reference call stack (SURVEY.md §3.2): fleet.init(strategy) builds
HybridCommunicateGroup + per-axis NCCL groups; fleet.distributed_model wraps
the model per active degrees (TensorParallel/PipelineParallel/DataParallel/
GroupSharded); fleet.distributed_optimizer wraps the optimizer.

TPU-native: `init` builds ONE named mesh; `distributed_model` records axes
(parameters already carry TP placements from the mp layers);
`make_train_step` compiles the whole step — forward, backward, clip, update —
into one jitted SPMD program whose in/out shardings encode DP, ZeRO stage
1/2/3, TP and SP simultaneously. XLA inserts and overlaps every collective
the reference hand-schedules in HybridParallelOptimizer/reducer/sharding hooks.
"""

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.nn.layer import Layer, functional_call
from paddle_tpu.parallel import sharding as sharding_mod
from paddle_tpu.parallel.strategy import DistributedStrategy
from paddle_tpu.parallel.topology import (
    HybridCommunicateGroup,
    set_hybrid_communicate_group,
    get_hybrid_communicate_group,
)
from paddle_tpu.parallel.data_parallel import DataParallel
from paddle_tpu.profiler.parts import part


class Fleet:
    def __init__(self):
        self._strategy: Optional[DistributedStrategy] = None
        self._hcg: Optional[HybridCommunicateGroup] = None
        self._is_initialized = False

    def init(self, role_maker=None, is_collective=True, strategy=None,
             devices=None):
        self._strategy = strategy or DistributedStrategy()
        self._hcg = HybridCommunicateGroup(strategy=self._strategy,
                                           devices=devices)
        set_hybrid_communicate_group(self._hcg)
        self._is_initialized = True
        # per-rank metric tagging: every metric created after fleet.init
        # carries this host's rank label, so per-rank writers under
        # parallel/launch.py emit distinguishable series into shared
        # JSONL/Prometheus sinks
        import os
        from paddle_tpu.observability.registry import set_default_labels
        set_default_labels(rank=os.environ.get("PADDLE_TRAINER_ID", "0"))
        return self

    @property
    def strategy(self):
        return self._strategy

    def get_hybrid_communicate_group(self):
        return self._hcg

    @property
    def mesh(self):
        return self._hcg.mesh if self._hcg else None

    def distributed_model(self, model: Layer):
        assert self._is_initialized, "call fleet.init first"
        hcg = self._hcg
        if hcg.get_pipe_parallel_world_size() > 1:
            from paddle_tpu.parallel.pipeline import PipelineParallel
            if not isinstance(model, PipelineParallel):
                model = PipelineParallel(model, hcg, self._strategy)
        elif hcg.get_data_parallel_world_size() > 1 and not isinstance(model, DataParallel):
            model = DataParallel(model)
        return model

    def distributed_optimizer(self, optimizer, strategy=None):
        return HybridParallelOptimizer(optimizer, self._hcg,
                                       strategy or self._strategy)

    # -- state placement -----------------------------------------------------

    def param_specs(self, model: Layer) -> Dict[str, P]:
        """Final parameter PartitionSpecs: TP placements from the layers,
        composed with ZeRO stage-3 sharding if enabled."""
        hcg, strat = self._hcg, self._strategy
        base = {}
        for name, p in model.named_parameters():
            base[name] = getattr(p, "pspec", None) or P()
        stage = strat.sharding_configs.stage if strat.sharding else 0
        degree = hcg.get_sharding_parallel_world_size()
        params = {n: p.value for n, p in model.named_parameters()}
        return sharding_mod.shard_params_spec(params, stage, degree,
                                              base_specs=base)

    def shard_model_state(self, model: Layer):
        """Place the model's trainable state onto the mesh per strategy."""
        specs = self.param_specs(model)
        state = model.trainable_state()
        placed = {k: jax.device_put(v, NamedSharding(self.mesh, specs[k]))
                  for k, v in state.items()}
        return placed, specs


class HybridParallelOptimizer:
    """API-shape veneer over the inner optimizer — it intentionally adds NO
    behavior. The reference class (meta_optimizers/dygraph_optimizer/
    hybrid_parallel_optimizer.py) exists to hand-fuse the grad-clip
    global-norm allreduces across dp/mp/pp/sharding groups; under GSPMD the
    clip in the inner optimizer already computes the global norm in one XLA
    reduction over the whole mesh, so there is nothing left to fuse. The
    class survives only so `fleet.distributed_optimizer(opt)` returns the
    reference's type shape."""

    def __init__(self, inner, hcg, strategy):
        self._inner = inner
        self._hcg = hcg
        self._strategy = strategy

    def __getattr__(self, name):
        return getattr(self._inner, name)

    @property
    def inner_opt(self):
        return self._inner


_fleet = Fleet()


def init(role_maker=None, is_collective=True, strategy=None, devices=None):
    return _fleet.init(role_maker, is_collective, strategy, devices)


def distributed_model(model):
    return _fleet.distributed_model(model)


def distributed_optimizer(optimizer, strategy=None):
    return _fleet.distributed_optimizer(optimizer, strategy)


def get_fleet() -> Fleet:
    return _fleet


def get_hybrid_communicate_group_():
    return _fleet.get_hybrid_communicate_group()


# ---- the compiled hybrid train step ---------------------------------------

def abstract_train_state(state0, pspecs, ospecs, optimizer, mesh,
                         scaler=None):
    """(abstract_state, abstract_opt) ShapeDtypeStructs with shardings —
    the shared AOT-lowering substrate of this module's and the pipeline
    engine's `step_fn.lower` hooks (one copy: an opt-state layout change
    must not silently diverge the two feasibility reports)."""
    abstract_state = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=NamedSharding(mesh, pspecs[k]))
        for k, v in state0.items()}
    abstract_opt = jax.eval_shape(optimizer.init_state, abstract_state)
    if scaler is not None:
        abstract_opt["scaler"] = jax.eval_shape(scaler.init_state)

    def shard_slot(tree):
        if isinstance(tree, dict):
            return {k: jax.ShapeDtypeStruct(
                v.shape, v.dtype,
                sharding=NamedSharding(mesh, ospecs.get(k, P())))
                for k, v in tree.items()}
        return tree
    return abstract_state, {slot: shard_slot(t)
                            for slot, t in abstract_opt.items()}

def make_train_step(model: Layer, optimizer, loss_fn: Callable,
                    strategy: Optional[DistributedStrategy] = None,
                    hcg: Optional[HybridCommunicateGroup] = None,
                    batch_axes=("dp", "sharding"),
                    donate: bool = True,
                    rng_streams=("dropout",)):
    """Build `(state, opt_state, batch, step) -> (state, opt_state, loss)` —
    one jitted SPMD program implementing the active parallelism strategy.

    * batch leading dim sharded over `batch_axes` (DP; the sharding axis also
      consumes batch — ZeRO semantics).
    * params/opt state sharded per strategy (stage 1/2/3 + TP placements).
    * loss_fn(outputs, batch) -> scalar loss.

    Returns (step_fn, init_fn): init_fn() places model + optimizer state.
    """
    strategy = strategy or _fleet.strategy or DistributedStrategy()
    hcg = hcg or _fleet.get_hybrid_communicate_group() or get_hybrid_communicate_group()
    if isinstance(model, DataParallel):
        model = model.inner_layer
    from paddle_tpu.parallel.pipeline import PipelineParallel
    if isinstance(model, PipelineParallel):
        model = model.inner_layer
    if hcg.get_pipe_parallel_world_size() > 1:
        if not hasattr(model, "pipeline_parts"):
            raise ValueError(
                f"pp_degree>1 but {type(model).__name__} does not implement "
                "pipeline_parts(); see parallel.pipeline.PipelineParts")
        if loss_fn is not None:
            raise ValueError(
                "pp_degree>1 computes the loss in the model's pipeline head "
                "(PipelineParts.head_apply); pass loss_fn=None")
        from paddle_tpu.parallel.pipeline import make_pipeline_train_step
        return make_pipeline_train_step(model, optimizer, strategy=strategy,
                                        hcg=hcg, donate=donate)
    mesh = hcg.mesh
    stage = strategy.sharding_configs.stage if strategy.sharding else 0
    degree = hcg.get_sharding_parallel_world_size()

    state0 = model.trainable_state()
    # LazyGuard (meta-init) models: shapes only — the AOT lower() path
    # works, init_fn raises loudly (mirrors the pipeline engine's guard)
    abstract = any(isinstance(v, jax.ShapeDtypeStruct)
                   for v in state0.values())

    # ---- AMP (strategy.amp, O2): params in low precision, fp32 masters in
    # the optimizer (multi_precision), dynamic loss scaling for fp16 ----
    amp_dt = None
    scaler = None
    if strategy.amp and strategy.amp_configs.level.upper() == "O2":
        from paddle_tpu.core.dtype import to_jax_dtype, is_floating
        amp_dt = to_jax_dtype(strategy.amp_configs.dtype)
        cast = (lambda v: jax.ShapeDtypeStruct(v.shape, amp_dt)) if abstract \
            else (lambda v: v.astype(amp_dt))
        state0 = {k: (cast(v) if is_floating(v.dtype) else v)
                  for k, v in state0.items()}
        if amp_dt == jnp.float16 and strategy.amp_configs.use_dynamic_loss_scaling:
            from paddle_tpu.amp import GradScaler
            scaler = GradScaler(
                init_loss_scaling=strategy.amp_configs.init_loss_scaling)

    base = {name: (getattr(p, "pspec", None) or P())
            for name, p in model.named_parameters() if p.trainable}
    pspecs = sharding_mod.shard_params_spec(state0, stage, degree,
                                            base_specs=base)
    ospecs = sharding_mod.opt_state_specs(pspecs, stage, degree, state0)
    gspecs = sharding_mod.grad_specs(pspecs, stage, degree, state0)

    active_batch_axes = tuple(a for a in batch_axes if hcg.axis_size(a) > 1)
    bspec = P(active_batch_axes if active_batch_axes else None)

    param_sh = {k: NamedSharding(mesh, s) for k, s in pspecs.items()}

    def opt_state_shardings(opt_state):
        def spec_for(path_key, leaf):
            return NamedSharding(mesh, ospecs.get(path_key, P()))
        sh = {}
        for slot, tree in opt_state.items():
            if isinstance(tree, dict):
                sh[slot] = {k: spec_for(k, v) for k, v in tree.items()}
            else:
                sh[slot] = NamedSharding(mesh, P())
        return sh

    remat_policy = None
    if strategy.recompute:
        from jax.ad_checkpoint import checkpoint_policies as cp
        remat_policy = {
            "full": cp.nothing_saveable,
            "nothing_saveable": cp.nothing_saveable,
            "dots_saveable": cp.dots_saveable,
            # reference recompute_granularity values — the models name
            # their matmul outputs (attn_qkv/ffn_gate/ffn_up); attn_out
            # is not saved (the flash bwd replays its fwd regardless)
            "full_attn": cp.save_only_these_names("ffn_gate", "ffn_up"),
            "core_attn": cp.save_only_these_names(
                "attn_qkv", "ffn_gate", "ffn_up"),
        }.get(strategy.recompute_configs.policy, cp.nothing_saveable)

    def forward_loss(state, batch, rngs):
        def fwd(s, b):
            # the model's forward names its own parts
            out = functional_call(model, s, b["input"] if isinstance(b, dict)
                                  and "input" in b else b, rngs=rngs)
            with part("loss"):
                return loss_fn(out, b)
        if remat_policy is not None:
            fwd = jax.checkpoint(fwd, policy=remat_policy)
        return fwd(state, batch)

    merge_k = (int(strategy.gradient_merge_configs.get("k_steps", 1))
               if strategy.gradient_merge else 1)

    def _value_and_grad(state, batch, rngs, scale=None):
        """Plain or gradient-merge (k-microbatch accumulated) grads."""
        def scalar_loss(s, b, r):
            l = forward_loss(s, b, r)
            if scale is None:
                return l
            with part("optimizer"):
                return l * scale

        if merge_k <= 1:
            return jax.value_and_grad(
                lambda s: scalar_loss(s, batch, rngs))(state)

        def split(x):
            if not hasattr(x, "ndim") or x.ndim == 0:
                # scalar leaves replicate so the scan can unstack them
                return jnp.broadcast_to(jnp.asarray(x), (merge_k,))
            if x.shape[0] % merge_k:
                raise ValueError(
                    f"gradient_merge k_steps={merge_k} does not divide "
                    f"batch dim {x.shape[0]}")
            return x.reshape((merge_k, x.shape[0] // merge_k) + x.shape[1:])
        micro = jax.tree_util.tree_map(split, batch)

        def body(acc, xs):
            mb, i = xs
            loss_acc, g_acc = acc
            # independent randomness per microbatch (≈ k separate steps)
            rngs_i = {name: jax.random.fold_in(k, i)
                      for name, k in (rngs or {}).items()}
            loss, g = jax.value_and_grad(
                lambda s: scalar_loss(s, mb, rngs_i))(state)
            with part("optimizer"):
                return (loss_acc + loss,
                        jax.tree_util.tree_map(jnp.add, g_acc, g)), None

        zero_g = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state)
        (loss_sum, g_sum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zero_g),
            (micro, jnp.arange(merge_k)))
        inv = 1.0 / merge_k
        with part("optimizer"):
            return (loss_sum * inv,
                    jax.tree_util.tree_map(lambda g: g * inv, g_sum))

    from paddle_tpu.ops import flash_attention
    from paddle_tpu.parallel.mp_layers import MP_AXIS
    head_axis = MP_AXIS if hcg.axis_size(MP_AXIS) > 1 else None

    def train_step(state, opt_state, batch, rngs):
        # the program's name: a run on a trace's `XLA Modules` line reads
        # `jit_train_step`. This trace is one GSPMD jit over `mesh`, which
        # cannot partition a Mosaic call: its flash kernels run per shard
        with flash_attention.partitioned(mesh, active_batch_axes,
                                         head_axis):
            return _step_on_mesh(state, opt_state, batch, rngs)

    def _step_on_mesh(state, opt_state, batch, rngs):
        if scaler is not None:
            sstate = opt_state["scaler"]
            loss_s, grads = _value_and_grad(state, batch, rngs,
                                            scale=sstate["scale"])
            with part("optimizer"):
                loss = loss_s / sstate["scale"]
                grads, found_inf = scaler.unscale(grads, sstate)
        else:
            loss, grads = _value_and_grad(state, batch, rngs)
        # constrain grads per stage-2 semantics; GSPMD propagates the rest
        grads = {k: jax.lax.with_sharding_constraint(
            g, NamedSharding(mesh, gspecs[k])) for k, g in grads.items()}
        with part("optimizer"):         # the clip is the optimizer's own
            new_state, new_opt = optimizer.update(grads, opt_state, state)
            if scaler is not None:
                # overflow step: keep old params/moments, only the scale
                # moves
                pick = lambda n, o: jnp.where(found_inf, o, n)
                new_state = jax.tree_util.tree_map(pick, new_state, state)
                new_opt = jax.tree_util.tree_map(pick, new_opt, opt_state)
                new_opt["scaler"] = scaler.update_state(sstate, found_inf)
        new_state = {k: jax.lax.with_sharding_constraint(
            v, NamedSharding(mesh, pspecs[k])) for k, v in new_state.items()}
        return new_state, new_opt, loss

    def init_fn():
        if abstract:
            raise RuntimeError(
                "this train step was built from a LazyGuard (meta-init) "
                "model — it has no parameter buffers to place; only the "
                "AOT step_fn.lower() feasibility path is available")
        # copy so the jit step's donation can never free the Layer's own
        # param buffers (device_put aliases when placement already matches)
        placed = {k: jax.device_put(jnp.array(v, copy=True), param_sh[k])
                  for k, v in state0.items()}
        opt_state = optimizer.init_state(placed)
        if scaler is not None:
            opt_state["scaler"] = scaler.init_state()
        opt_state = jax.device_put(opt_state, opt_state_shardings(opt_state))
        return placed, opt_state

    jit_step = jax.jit(
        train_step,
        donate_argnums=(0, 1) if donate else (),
    )

    batch_degree = 1
    for a in active_batch_axes:
        batch_degree *= hcg.axis_size(a)

    def _place_batch_leaf(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        x = jnp.asarray(x)
        spec0 = bspec[0]
        if batch_degree > 1 and x.shape[0] % batch_degree:
            import warnings
            warnings.warn(
                f"batch dim {x.shape[0]} not divisible by dp×sharding="
                f"{batch_degree}: replicating this array (no data "
                "parallelism for it)", stacklevel=3)
            spec0 = None
        return jax.device_put(x, NamedSharding(
            mesh, P(*([spec0] + [None] * (x.ndim - 1)))))

    def step_fn(state, opt_state, batch, rngs=None):
        if rngs is None:
            from paddle_tpu.core import rng as rng_mod
            rngs = {name: rng_mod.global_key() for name in rng_streams}
        batch = jax.tree_util.tree_map(_place_batch_leaf, batch)
        return jit_step(state, opt_state, batch, rngs)

    def lower(batch_shape, seq_len, ids_dtype=jnp.int32):
        """AOT-lower the compiled step from abstract shapes (no real
        buffers) — .compile().memory_analysis() gives the per-device
        accounting for feasibility reports (SCALE.md), mirroring the
        pipeline engine's hook."""
        abstract_state, abstract_opt = abstract_train_state(
            state0, pspecs, ospecs, optimizer, mesh, scaler=scaler)
        bsh = NamedSharding(mesh, P(bspec[0], None))
        abstract_batch = {
            "input": jax.ShapeDtypeStruct((batch_shape, seq_len), ids_dtype,
                                          sharding=bsh),
            "labels": jax.ShapeDtypeStruct((batch_shape, seq_len), ids_dtype,
                                           sharding=bsh)}
        abstract_rngs = {name: jax.eval_shape(
            lambda: jax.random.PRNGKey(0)) for name in rng_streams}
        return jit_step.lower(abstract_state, abstract_opt, abstract_batch,
                              abstract_rngs)

    step_fn.lower = lower

    return step_fn, init_fn
