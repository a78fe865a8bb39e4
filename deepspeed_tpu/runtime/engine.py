"""DeepSpeedEngine: the core training runtime.

TPU-native analog of ``deepspeed/runtime/engine.py:182``. The reference engine
wraps a torch module and hand-schedules collectives (bucketed allreduce,
ZeRO reduce-scatter pumps, allgather prefetch). Here the engine compiles ONE
train step over the global mesh:

- ZeRO stages are *sharding layouts* (``parallel/sharding.py``): the step's
  in/out shardings for params / optimizer state / gradients make XLA emit the
  identical collective schedule the reference hand-codes — allreduce (stage 0),
  shard-local update + param allgather (stage 1), grad reduce-scatter
  (stage 2), JIT param allgather with latency-hiding prefetch (stage 3).
- Gradient accumulation is ``lax.scan`` over a leading microbatch dim
  (reference GAS boundary logic: ``engine.py:2060``).
- fp16 dynamic loss scaling and overflow-skip run inside the step
  (``fp16/loss_scaler.py``), no host sync.

API parity: ``forward/backward/step``, ``train_batch``,
``save_checkpoint/load_checkpoint``, plus the fused ``train_step`` fast path.
"""

import functools
import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from ..models import as_model
from ..ops.optimizers import Optimizer, build_optimizer
from ..parallel import sharding as shd
from ..utils import groups
from ..utils.logging import log_dist, logger
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER, NoopTimer,
                           STEP_GLOBAL_TIMER, SynchronizedWallClockTimer, ThroughputTimer)
from .config import DeepSpeedConfig
from .fp16.loss_scaler import (LossScaleState, StaticLossScaler, create_loss_scaler,
                               has_overflow)
from .lr_schedules import LRSchedule, build_lr_schedule

MEMORY_OPT_ALLREDUCE_SIZE = 500_000_000


def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _tree_zeros_like(t, dtype=None):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, dtype or x.dtype), t)


def _twinflow_host_mask(leaves, ratio):
    """Pick which param leaves carry host optimizer state under Twin-Flow
    partial offload: largest-first greedy until >= ratio of total elements
    (reference ZeRO-Offload++ splits the flat partition at the same
    fraction). Returns a bool list aligned with the flattened leaf order."""
    sizes = [int(p.size) for p in leaves]
    target = ratio * sum(sizes)
    mask = [False] * len(leaves)
    acc = 0
    for i in sorted(range(len(leaves)), key=lambda i: -sizes[i]):
        if acc >= target:
            break
        mask[i] = True
        acc += sizes[i]
    return mask


def _global_norm(tree):
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


class DeepSpeedEngine:
    """Compiled-step training engine over the global device mesh."""

    def __init__(self,
                 args=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 collate_fn=None,
                 config=None,
                 dont_change_device=False):
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._cached = None          # (loss, grads) from forward, consumed by backward
        self._acc_grads = None
        self._acc_count = 0
        self._pending_overflow = []  # device flags, drained at steps_per_print
        self._eval_fn = None

        if not dist.is_initialized():
            dist.init_distributed(verbose=False)
        self.mesh = groups.get_mesh()
        self.dp_world_size = groups.get_data_parallel_world_size()
        self.mp_world_size = groups.get_model_parallel_world_size()

        self._config = config if isinstance(config, DeepSpeedConfig) else \
            DeepSpeedConfig(config, world_size=self.dp_world_size)
        if self._config.world_size is None:
            self._config._configure_train_batch_size(self.dp_world_size)
            self._config.world_size = self.dp_world_size

        self.model = as_model(model)
        self._maybe_override_model_dtype()

        self.zero_stage = self._config.zero_optimization_stage
        self.offload_optimizer = (self._config.zero_config.offload_optimizer is not None and
                                  self._config.zero_config.offload_optimizer.device != "none")

        # ---- shardings ----
        abstract = self.model.abstract_params()
        logical = self.model.logical_axes()
        self._hpz = (self._config.zero_config.zero_hpz_partition_size > 1
                     and self.mesh.shape.get("zrep", 1) > 1)
        self.param_shardings = shd.tree_shardings(abstract, logical,
                                                  shd.zero_rules(self.zero_stage), self.mesh)
        if self.zero_stage == 3:
            # stage3_param_persistence_threshold (reference
            # partition_parameters.py persisted params): leaves smaller than
            # the threshold stay replicated over the ZeRO axes — tiny
            # norms/biases skip the per-layer allgather entirely.
            zo_dict = self._config._param_dict.get("zero_optimization", {})
            explicit = ("stage3_param_persistence_threshold" in zo_dict
                        or "param_persistence_threshold" in zo_dict)
            thr = int(self._config.zero_config.param_persistence_threshold or 0)
            if explicit and thr > 0:
                import math as _math
                small = shd.tree_shardings(abstract, logical,
                                           shd.zero_rules(1), self.mesh)
                self.param_shardings = jax.tree.map(
                    lambda s3, s1, a: s1 if _math.prod(a.shape) < thr else s3,
                    self.param_shardings, small, abstract,
                    is_leaf=lambda x: isinstance(x, NamedSharding))
        self._opt_param_shardings = shd.tree_shardings(
            abstract, logical,
            shd.optimizer_state_rules(self.zero_stage, hpz=self._hpz), self.mesh)
        # grads: stage>=2 reduce-scattered into the optimizer layout, else like params
        self.grad_shardings = self._opt_param_shardings if self.zero_stage >= 2 else self.param_shardings
        # Inside the (scanned) backward, constrain grads over "data" only:
        # a joint (data, seq/expert) embed sharding as the scan-output target
        # makes XLA's propagation demand embed-sharded activations inside the
        # layer loop ("involuntary full rematerialization"). The full joint
        # layout is applied in a second hop outside the loop (cheap reshard
        # of already-reduced grads).
        # (stage 3 grads already arrive in the params' FSDP layout — only the
        # stage-2 replicated-params/joint-sharded-grads combination conflicts)
        joint = (self.mesh.shape.get("seq", 1) > 1 or self.mesh.shape.get("expert", 1) > 1)
        if self.zero_stage == 2 and joint:
            data_only = tuple(("embed", ("data",)) if r[0] == "embed" else r
                              for r in shd.BASE_RULES)
            self._grad_inner_shardings = shd.tree_shardings(abstract, logical,
                                                            data_only, self.mesh)
        else:
            self._grad_inner_shardings = self.grad_shardings
        self._replicated = NamedSharding(self.mesh, P())
        self.batch_sharding = NamedSharding(self.mesh, shd.batch_spec(self.mesh))

        # ---- ZeRO-Infinity layer streaming (params on host / NVMe) ----
        self._infinity = None
        off_p = self._config.zero_config.offload_param
        if off_p is None or off_p.device == "none":
            # an enclosing zero.Init(remote_device=...) implies param offload
            from .zero import _active_init_remote_device
            rd = _active_init_remote_device()
            if rd and rd != "none" and self.zero_stage == 3:
                from .zero.config import DeepSpeedZeroOffloadParamConfig
                off_p = DeepSpeedZeroOffloadParamConfig(device=rd)
        if self.zero_stage == 3 and off_p is not None and off_p.device != "none":
            self._init_infinity(off_p)
            return

        # ---- parameters ----
        seed = int(self._config._param_dict.get("seed", 42))
        init_rng = jax.random.PRNGKey(seed)
        with self.mesh:
            self.module_params = jax.jit(self.model.init,
                                         out_shardings=self.param_shardings)(init_rng)

        # ---- optimizer ----
        self.optimizer = self._configure_optimizer(optimizer)
        self.opt_state_shardings = self._build_opt_state_shardings(abstract)
        self._host_optimizer = None
        self._twinflow = None
        off_o = self._config.zero_config.offload_optimizer
        if off_o is not None and off_o.device == "cpu" and off_o.native:
            # ZeRO-Offload with the NATIVE host kernel: fp32 masters/moments
            # as host numpy, updated by csrc CPUAdam; only grads/params cross
            # the host-device boundary (reference stage_1_and_2.py:1189).
            from .zero.offload_host import HostOffloadOptimizer
            ratio = float(getattr(off_o, "ratio", 1.0))
            # host state is sharded: each process materializes only its
            # addressable slices of the optimizer layout (reference shards
            # CPU optimizer state per DP rank, stage_1_and_2.py:1189)
            host_tree = self._to_opt_layout(self.module_params)
            if ratio < 1.0:
                # Twin-Flow (ZeRO-Offload++, blogs/deepspeed-offloadpp):
                # only `ratio` of the optimizer state lives on host; the
                # rest stays on the accelerator with a compiled update, so
                # host-update latency shrinks proportionally.
                flat, treedef = jax.tree.flatten(host_tree)
                mask = _twinflow_host_mask(flat, ratio)
                host_masked = treedef.unflatten(
                    [p if m else None for p, m in zip(flat, mask)])
                self._host_optimizer = HostOffloadOptimizer(
                    self.optimizer.hyper, host_masked, self._opt_param_shardings,
                    gradient_clipping=float(self._config.gradient_clipping or 0.0),
                    optimizer_name=self.optimizer.name)
                dev_flat = jax.tree.leaves(self.module_params)
                dev_masked = treedef.unflatten(
                    [p if not m else None for p, m in zip(dev_flat, mask)])
                with self.mesh:
                    dev_state = jax.jit(self.optimizer.init)(dev_masked)
                self._twinflow = {"mask": mask, "treedef": treedef,
                                  "dev_state": dev_state}
                host_elems = sum(p.size for p, m in zip(flat, mask) if m)
                total = sum(p.size for p in flat)
                log_dist(
                    f"ZeRO-Offload++ Twin-Flow: ratio={ratio} → "
                    f"{host_elems / total:.2%} of optimizer state on host, "
                    "rest updated on device", ranks=[0])
            else:
                self._host_optimizer = HostOffloadOptimizer(
                    self.optimizer.hyper, host_tree, self._opt_param_shardings,
                    gradient_clipping=float(self._config.gradient_clipping or 0.0),
                    optimizer_name=self.optimizer.name)
                log_dist("ZeRO-Offload: native host CPUAdam in the step loop "
                         f"({self._host_optimizer.local_element_count():,} "
                         "master elements on this process)", ranks=[0])
            # host-offloaded state lives inside _host_optimizer (sharded
            # per process); snapshot via _host_optimizer.state_dict()
            self.opt_state = None
        else:
            with self.mesh:
                self.opt_state = jax.jit(self.optimizer.init,
                                         out_shardings=self.opt_state_shardings)(self.module_params)

        # ---- precision / loss scaling ----
        # NVMe optimizer offload: state parked on disk between steps
        self._opt_swapper = None
        off = self._config.zero_config.offload_optimizer
        if off is not None and off.device == "nvme":
            from .swap_tensor.swapper import OptimizerSwapper
            swap_dir = os.path.join(off.nvme_path or "/tmp/ds_tpu_nvme", "optimizer")
            self._opt_swapper = OptimizerSwapper(swap_dir)
            self._opt_swapper.swap_out_optimizer(jax.device_get(self.opt_state))
            self.opt_state = None
            log_dist(f"Optimizer state swapped to NVMe at {swap_dir}", ranks=[0])

        self.loss_scaler = create_loss_scaler(self._config.fp16, self._config.precision_dtype)
        # placed on the mesh like the state the step returns: fresh
        # default-device scalars would type differently from step 1's
        # outputs and compile the whole step a second time at step 2
        self.scaler_state = jax.device_put(self.loss_scaler.init_state(),
                                           self._replicated)
        self.gradient_clipping = float(self._config.gradient_clipping or 0.0)

        # ---- lr schedule ----
        self.lr_scheduler = self._configure_lr_scheduler(lr_scheduler)
        self.client_lr_scheduler = lr_scheduler

        # ---- data ----
        self.training_dataloader = self._configure_dataloader(training_data, collate_fn)

        # ---- timers / monitor ----
        self.wall_clock_breakdown = self._config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer() if self.wall_clock_breakdown else NoopTimer()
        self.tput_timer = ThroughputTimer(batch_size=self.train_batch_size(),
                                          steps_per_output=self._config.steps_per_print)
        self.monitor = self._configure_monitor()
        dist.configure(self._config)

        self._compile_step_fns()
        self._checkpoint_engine = None
        log_dist(f"DeepSpeedEngine ready: zero_stage={self.zero_stage} "
                 f"mesh={dict(zip(self.mesh.axis_names, self.mesh.devices.shape))} "
                 f"micro_bs={self.train_micro_batch_size_per_gpu()} gas={self.gradient_accumulation_steps()} "
                 f"dtype={self._config.precision_dtype.__name__ if hasattr(self._config.precision_dtype, '__name__') else self._config.precision_dtype}",
                 ranks=[0])

    def _init_infinity(self, off_p):
        """Bring up the ZeRO-Infinity layer-streaming runner (params + master
        weights + optimizer state resident on host or NVMe; see
        ``runtime/zero/infinity.py``) and the subset of engine services it
        needs. The compiled-step path is not built in this mode."""
        from .zero.infinity import InfinityRunner
        opt_cfg = self._config.optimizer
        hyper = dict(opt_cfg.params) if opt_cfg and opt_cfg.params else {"lr": 1e-3}
        nvme = None
        if off_p.device == "nvme":
            nvme = os.path.join(off_p.nvme_path or "/tmp/ds_tpu_nvme", "params")
        group_layers = max(1, int(self._config._param_dict.get(
            "zero_optimization", {}).get("stream_group_layers", 1)))
        seed = int(self._config._param_dict.get("seed", 42))
        self._infinity = InfinityRunner(self.model, self.mesh, hyper,
                                        group_layers=group_layers, nvme_path=nvme,
                                        buffer_count=off_p.buffer_count, seed=seed,
                                        gradient_clipping=float(
                                            self._config.gradient_clipping or 0.0))
        self.module_params = None
        self.optimizer = None
        self.opt_state = None
        self._opt_swapper = None
        self.loss_scaler = create_loss_scaler(self._config.fp16, self._config.precision_dtype)
        # placed on the mesh like the state the step returns: fresh
        # default-device scalars would type differently from step 1's
        # outputs and compile the whole step a second time at step 2
        self.scaler_state = jax.device_put(self.loss_scaler.init_state(),
                                           self._replicated)
        self.gradient_clipping = float(self._config.gradient_clipping or 0.0)
        self.lr_scheduler = self._configure_lr_scheduler(None)
        self.client_lr_scheduler = None
        self.training_dataloader = None
        self.wall_clock_breakdown = self._config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer() if self.wall_clock_breakdown else NoopTimer()
        self.tput_timer = ThroughputTimer(batch_size=self.train_batch_size(),
                                          steps_per_output=self._config.steps_per_print)
        self.monitor = self._configure_monitor()
        self._checkpoint_engine = None
        log_dist(f"DeepSpeedEngine ready (ZeRO-Infinity streaming): "
                 f"groups={self._infinity.n_groups} x {self._infinity.group_layers} layers, "
                 f"residence={'nvme' if nvme else 'cpu'}", ranks=[0])

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------

    def _maybe_override_model_dtype(self):
        from ..models.transformer import CausalLM
        # overrides must land on the object whose forward READS cfg: for
        # wrappers delegating to a CausalLM (DistilledModel) that is the
        # wrapped student, not the wrapper (setting wrapper.cfg would
        # shadow-attribute it and silently change nothing)
        target = self.model
        if not isinstance(target, CausalLM) and isinstance(
                getattr(target, "student", None), CausalLM):
            target = target.student
        if isinstance(target, CausalLM):
            dt = self._config.precision_dtype
            name = {jnp.float16: "float16", jnp.bfloat16: "bfloat16"}.get(dt)
            if name and target.cfg.dtype != name:
                target.cfg = target.cfg.replace(dtype=name)
            ac = self._config.activation_checkpointing
            if ac.policy != "none" and target.cfg.remat == "none":
                target.cfg = target.cfg.replace(remat=ac.policy)
            if ac.cpu_checkpointing and target.cfg.remat in ("none", "dots",
                                                             "dots_no_batch"):
                # reference cpu_checkpointing: saved matmul outputs parked in
                # host memory, streamed back for the backward
                target.cfg = target.cfg.replace(remat="dots_offload")
            if ac.partition_activations and not target.cfg.partition_activations:
                target.cfg = target.cfg.replace(partition_activations=True)

    def _configure_optimizer(self, client_optimizer) -> Optimizer:
        opt = self._build_base_optimizer(client_optimizer)
        # fp32 master weights for low-precision training (reference
        # BF16_Optimizer / FP16_Optimizer keep hp params;
        # runtime/bf16_optimizer.py:34). fp16_master_weights_and_grads
        # opts out for fp16 (reference stage_1_and_2.py fp16 master mode).
        dt = self._config.precision_dtype
        if dt == jnp.bfloat16:
            opt.master_weights = self._config.bf16.master_weights
        elif dt == jnp.float16:
            opt.master_weights = not self._config.fp16.fp16_master_weights_and_grads
        return opt

    def _build_base_optimizer(self, client_optimizer) -> Optimizer:
        if isinstance(client_optimizer, Optimizer):
            log_dist("Using client Optimizer instance", ranks=[0])
            return client_optimizer
        if isinstance(client_optimizer, str):
            return build_optimizer(client_optimizer, {})
        opt_cfg = self._config.optimizer
        if opt_cfg.type is None:
            return build_optimizer("adamw", {"lr": 1e-3})
        name = opt_cfg.type
        params = dict(opt_cfg.params)
        # honor offload: cpu_* is the same math, placement handled by the
        # engine (reference csrc/{adam,adagrad,lion} host-kernel set)
        if self.offload_optimizer:
            key = name.lower().replace("_", "").replace("-", "")
            name = {"adam": "cpuadam", "adamw": "cpuadam",
                    "fusedadam": "cpuadam", "adagrad": "cpuadagrad",
                    "lion": "cpulion"}.get(key, name)
        return build_optimizer(name, params)

    def _configure_lr_scheduler(self, client_scheduler) -> Optional[LRSchedule]:
        if client_scheduler is not None:
            if isinstance(client_scheduler, LRSchedule):
                return client_scheduler
            if callable(client_scheduler):
                # factory(optimizer) or plain callable(step)->lr
                return client_scheduler
            return client_scheduler
        sched_cfg = self._config.scheduler
        if sched_cfg.type is None:
            return None
        default_lr = self.optimizer.hyper.get("lr")
        return build_lr_schedule(sched_cfg.type, sched_cfg.params, default_lr)

    def _configure_dataloader(self, training_data, collate_fn):
        if training_data is None:
            return None
        from .dataloader import DeepSpeedDataLoader
        return DeepSpeedDataLoader(training_data,
                                   batch_size=self.train_micro_batch_size_per_gpu(),
                                   collate_fn=collate_fn,
                                   drop_last=self._config.dataloader_drop_last)

    def _configure_monitor(self):
        try:
            from ..monitor.monitor import MonitorMaster
            return MonitorMaster(self._config.monitor_config)
        except Exception:
            return None

    def _build_opt_state_shardings(self, abstract_params):
        abstract_opt = jax.eval_shape(self.optimizer.init, abstract_params)
        flat_shard, treedef = jax.tree.flatten(self._opt_param_shardings,
                                               is_leaf=lambda x: isinstance(x, NamedSharding))
        flat_slots = treedef.flatten_up_to(abstract_opt["slots"])
        slot_shardings = treedef.unflatten([
            jax.tree.map(lambda _: sh, slot) for sh, slot in zip(flat_shard, flat_slots)
        ])
        shardings = {"step": self._replicated, "slots": slot_shardings}
        # ZeRO-Offload: optimizer state lives in host memory; the update
        # stages it through device memory (reference: CPUAdam on pinned
        # buffers, stage_1_and_2.py:1189 grad offload path).
        self._opt_device_shardings = shardings
        off = self._config.zero_config.offload_optimizer
        if off is not None and off.device == "cpu" and self._host_memory_kind():
            kind = self._host_memory_kind()
            shardings = jax.tree.map(lambda s: s.with_memory_kind(kind), shardings,
                                     is_leaf=lambda x: isinstance(x, NamedSharding))
        return shardings

    def _reshard_tree(self, tree, target_shardings):
        """Compiled-identity reshard of a param-shaped tree (the ZeRO-Offload
        staging allgather/slice; rides ICI). Trees with None leaves (Twin-Flow
        halves) pass through. The jitted identity is memoized per (treedef,
        shardings) — a fresh jax.jit each step would retrace and recompile in
        the hot path."""
        shardings = jax.tree.map(
            lambda p, s: None if p is None else s, tree, target_shardings,
            is_leaf=lambda x: x is None)
        leaves, treedef = jax.tree.flatten(shardings)
        key = (treedef, tuple(leaves))
        cache = getattr(self, "_reshard_fns", None)
        if cache is None:
            cache = self._reshard_fns = {}
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = jax.jit(lambda t: t, out_shardings=shardings)
        with self.mesh:
            return fn(tree)

    def _to_opt_layout(self, param_tree):
        """Reshard params into the optimizer layout (each rank's slice)."""
        return self._reshard_tree(param_tree, self._opt_param_shardings)

    def _to_param_layout(self, tree):
        """Reshard optimizer-layout arrays back to the training param layout
        (the ZeRO-Offload re-staging allgather)."""
        return self._reshard_tree(tree, self.param_shardings)

    def _host_memory_kind(self):
        # Only meaningful on a real accelerator: on the CPU backend all
        # memory IS host memory (and its SPMD partitioner rejects the
        # placement annotation anyway).
        if jax.default_backend() != "tpu":
            return None
        try:
            kinds = {m.kind for m in self.mesh.devices.flat[0].addressable_memories()}
        except Exception:
            return None
        for kind in ("pinned_host", "unpinned_host"):
            if kind in kinds:
                return kind
        return None

    # ------------------------------------------------------------------
    # compiled step functions
    # ------------------------------------------------------------------

    def _loss_and_grads(self, params, batch, scale):
        """Single-microbatch scaled loss + grads with ZeRO grad layout."""
        if self._zeropp_enabled:
            return self._zeropp_loss_and_grads(params, batch, scale)
        def scaled_loss(p):
            loss = self.model.loss(p, batch)
            return loss * scale
        loss, grads = jax.value_and_grad(scaled_loss)(params)
        grads = jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s), grads,
            self._grad_inner_shardings)
        return loss / scale, grads

    # ------------------------------------------------------------------
    # ZeRO++ (qwZ / qgZ): quantized collectives in the compiled step
    # ------------------------------------------------------------------

    @property
    def _zeropp_enabled(self) -> bool:
        zc = self._config.zero_config
        return ((zc.zero_quantized_weights or zc.zero_quantized_gradients)
                and self.zero_stage >= 2 and self.mesh.shape["data"] > 1)

    @staticmethod
    def _data_dim(spec) -> Optional[int]:
        """Index of the dim a PartitionSpec shards over the 'data' axis."""
        for i, part in enumerate(spec):
            axes = (part,) if isinstance(part, str) else tuple(part or ())
            if "data" in axes:
                return i
        return None

    def _zeropp_loss_and_grads(self, params, batch, scale):
        """Loss + grads through explicit quantized collectives (ZeRO++).

        A shard_map manual region over the ``data`` axis replaces XLA's
        sharding-derived collectives: ZeRO-3 param shards are gathered with
        int8 on the wire (qwZ, reference ``engine.py:901``) via a custom_vjp
        whose backward is the int8 gradient reduce-scatter (qgZ, reference
        ``runtime/comm/coalesced_collectives.py:31``). value_and_grad runs
        INSIDE the manual region so gradients stay rank-local until the
        explicit (quantized) reduction.
        """
        from .comm.coalesced_collectives import (quantized_reduce_scatter_along_dim,
                                                 reduce_scatter_along_dim,
                                                 zeropp_param_gather)

        zc = self._config.zero_config
        qw = bool(zc.zero_quantized_weights)
        qg = bool(zc.zero_quantized_gradients)
        mesh = self.mesh
        # expert/seq axes compose with the data-manual region: the quantized
        # collectives are manual over "data" only, while expert dispatch and
        # Ulysses head-swaps ride the auto axes inside the region (their
        # sharding-constraint anchors skip manual-varying values — see
        # _activation_constraint / apply_moe_mlp's current_manual_axes guard)

        leaves, treedef = jax.tree.flatten(self.param_shardings)
        p_dims = [self._data_dim(s.spec) for s in leaves]
        o_leaves = jax.tree.leaves(self._opt_param_shardings)
        o_dims = [self._data_dim(s.spec) for s in o_leaves]

        def strip(dim, ndim):
            return P(*[("data" if i == dim else None) for i in range(ndim)])

        abstract = jax.tree.leaves(self.model.abstract_params())
        param_in_specs = treedef.unflatten(
            [strip(d, len(a.shape)) for d, a in zip(p_dims, abstract)])
        grad_out_specs = treedef.unflatten(
            [strip(d if d is not None else od, len(a.shape))
             if (d is not None or od is not None) else P(None)
             for d, od, a in zip(p_dims, o_dims, abstract)])
        batch_in_specs = jax.tree.map(lambda _: P("data"), batch)

        def body(params, batch, scale):
            flat_p = treedef.flatten_up_to(params)

            def local_loss(flat_shards):
                # gather INSIDE the differentiated function: its custom VJP
                # reduce-scatters the cotangent back to shards (qgZ)
                full = [zeropp_param_gather(p, d, "data", qw, qg)
                        if d is not None else p for p, d in zip(flat_shards, p_dims)]
                return self.model.loss(treedef.unflatten(full), batch) * scale

            loss, grads = jax.value_and_grad(local_loss)(flat_p)
            out = []
            for g, d, od in zip(grads, p_dims, o_dims):
                if d is not None:
                    out.append(g)  # already reduce-scattered by the gather VJP
                elif od is not None:
                    # stage-2 layout: grads land in the optimizer sharding
                    if qg:
                        out.append(quantized_reduce_scatter_along_dim(g, od, "data")
                                   .astype(g.dtype))
                    else:
                        out.append(reduce_scatter_along_dim(
                            g.astype(jnp.float32), od, "data").astype(g.dtype))
                else:
                    out.append(jax.lax.psum(g, "data"))
            return jax.lax.pmean(loss, "data"), treedef.unflatten(out)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(param_in_specs, batch_in_specs, P()),
                           out_specs=(P(), grad_out_specs),
                           axis_names={"data"})
        loss, grads = fn(params, batch, jnp.asarray(scale, jnp.float32))
        grads = jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, self.grad_shardings)
        return loss / scale, grads

    @property
    def _needs_overflow_check(self) -> bool:
        """fp16 training skips the step on inf/nan grads (reference
        ``engine.py:2150-2157``); for bf16/fp32 the machinery (is-finite
        reduction + full-tree selects, real HBM traffic each step) is
        compiled out unless ``bf16.check_grad_overflow`` opts back in
        (reference BF16_Optimizer check_overflow)."""
        if self._config.precision_dtype == jnp.float16:
            return True
        return bool(self._config.bf16.check_grad_overflow)

    def _apply_update(self, params, opt_state, scaler_state, grads, lr, grad_divisor):
        """Unscale, clip, overflow-check, optimizer apply (or skip)."""
        host_offload = self.opt_state_shardings is not self._opt_device_shardings
        if host_offload:  # stage host-resident state into device memory
            opt_state = jax.device_put(opt_state, self._opt_device_shardings)
        static_one = (isinstance(self.loss_scaler, StaticLossScaler)
                      and self.loss_scaler.scale == 1.0
                      and isinstance(grad_divisor, (int, float)) and grad_divisor == 1)
        if static_one:
            # scale and divisor are compile-time 1.0: no unscale pass at all
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            inv = 1.0 / (scaler_state.scale * grad_divisor)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, grads)
        check_overflow = self._needs_overflow_check
        overflow = has_overflow(grads) if check_overflow else jnp.zeros((), bool)
        if self.gradient_clipping > 0.0:
            grad_norm = _global_norm(grads)
            coef = jnp.minimum(1.0, self.gradient_clipping / (grad_norm + 1e-6))
            grads = jax.tree.map(lambda g: g * coef, grads)
        else:
            grad_norm = jnp.zeros((), jnp.float32)
        new_params, new_opt = self.optimizer.apply(grads, opt_state, params, lr=lr)
        if check_overflow:
            # skip the update on overflow (fp16): select old state
            new_params = jax.tree.map(lambda n, o: jnp.where(overflow, o, n), new_params, params)
            new_opt = jax.tree.map(lambda n, o: jnp.where(overflow, o, n), new_opt, opt_state)
        new_scaler = self.loss_scaler.update(scaler_state, overflow)
        if host_offload:  # results stream back to pinned host buffers
            new_opt = jax.device_put(new_opt, self.opt_state_shardings)
        return new_params, new_opt, new_scaler, overflow, grad_norm

    def _compile_step_fns(self):
        mesh = self.mesh
        self.pipe_parallel_size = mesh.shape["pipe"]
        if self.pipe_parallel_size > 1:
            if self._host_optimizer is not None:
                raise NotImplementedError(
                    "pipeline parallelism with native CPU-offload optimizer "
                    "is not supported; set offload_optimizer.native=false")
            self._compile_pipeline_step_fns()
            return
        if self._host_optimizer is not None:
            self._compile_host_offload_step_fns()
            return
        self._onebit = getattr(self.optimizer, "name", "").startswith(("onebit", "zero_one"))
        if self._onebit:
            self._prepare_onebit()
        self._sparse_grads = bool(getattr(self._config,
                                          "sparse_gradients_enabled", False))
        if self._sparse_grads:
            self._prepare_sparse_grads()

        @functools.partial(jax.jit,
                           out_shardings=(self._replicated, self.grad_shardings))
        def grad_fn(params, batch, scale):
            return self._loss_and_grads(params, batch, scale)

        @functools.partial(
            jax.jit,
            donate_argnums=(0, 1, 2),
            out_shardings=(self.param_shardings, self.opt_state_shardings, None,
                           self._replicated, self._replicated))
        def update_fn(params, opt_state, scaler_state, grads, lr, grad_divisor):
            with jax.named_scope("optimizer"):
                return self._apply_update(params, opt_state, scaler_state,
                                          grads, lr, grad_divisor)

        @functools.partial(
            jax.jit,
            donate_argnums=(0, 1, 2),
            static_argnames=("gas",),
            out_shardings=(self.param_shardings, self.opt_state_shardings, None,
                           self._replicated, self._replicated, self._replicated))
        def train_step_fn(params, opt_state, scaler_state, batch, lr, gas):
            """Fused step: scan over gas microbatches then update.

            batch leaves have leading dim (gas, micro_bs, ...).
            """
            scale = scaler_state.scale

            if gas == 1:
                # fast path: no accumulation buffers, grads stay in param
                # dtype until the fp32 cast inside the update
                mb = jax.tree.map(lambda x: x[0], batch)
                loss_sum, acc = self._loss_and_grads(params, batch=mb, scale=scale)
                divisor = 1
            else:
                def micro(carry, mb):
                    acc, loss_sum = carry
                    loss, grads = self._loss_and_grads(params, batch=mb, scale=scale)
                    return (_tree_add(acc, grads), loss_sum + loss), None

                acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
                acc0 = jax.tree.map(lambda g, s: jax.lax.with_sharding_constraint(g, s),
                                    acc0, self._grad_inner_shardings)
                (acc, loss_sum), _ = jax.lax.scan(micro, (acc0, jnp.zeros((), jnp.float32)), batch)
                divisor = float(gas)
            # second hop: full ZeRO grad layout (data × seq/expert), outside
            # the loops so the reshard is a one-shot exchange
            acc = jax.tree.map(lambda g, s: jax.lax.with_sharding_constraint(g, s),
                               acc, self.grad_shardings)
            with jax.named_scope("optimizer"):
                new_params, new_opt, new_scaler, overflow, grad_norm = \
                    self._apply_update(params, opt_state, scaler_state, acc,
                                       lr, divisor)
            return new_params, new_opt, new_scaler, loss_sum / gas, overflow, grad_norm

        self._grad_fn = grad_fn
        self._update_fn = update_fn
        self._train_step_fn = train_step_fn

    def _prepare_sparse_grads(self):
        """Sparse (row-wise) embedding-gradient allreduce (reference
        ``engine.py:2518 sparse_allreduce_bucket``; config
        ``sparse_gradients``): the embedding table's gradient rides a
        touched-rows all-gather over the data axis instead of the dense
        (V, E) allreduce. Like the reference's torch-sparse grads this
        needs the table's grad to come only from input lookups."""
        from ..models.transformer import CausalLM
        if self.zero_stage > 1:
            raise NotImplementedError(
                "sparse_gradients requires zero_optimization.stage <= 1 "
                "(stages 2/3 reduce-scatter into sharded grad layouts)")
        for ax in ("tensor", "pipe", "seq", "expert", "zrep"):
            if self.mesh.shape.get(ax, 1) > 1:
                raise NotImplementedError(
                    f"sparse_gradients supports a pure data mesh (got {ax}>1)")
        if isinstance(self.model, CausalLM) and self.model.cfg.tie_embeddings:
            raise NotImplementedError(
                "sparse_gradients is incompatible with tied embeddings: the "
                "lm-head contribution makes the table's gradient dense "
                "(reference restriction: only sparse=True embedding layers)")
        if self._config.fp16.enabled:
            raise NotImplementedError("sparse_gradients requires bf16/fp32")
        paths = [jax.tree_util.keystr(kp) for kp, _ in
                 jax.tree_util.tree_flatten_with_path(self.module_params)[0]]
        if not any("embed" in p and "tok" in p for p in paths):
            raise NotImplementedError(
                "sparse_gradients needs an embedding table at "
                "params['embed']['tok'] (the leaf whose gradient is "
                "row-sparse); this model has none")
        self._sparse_grad_fn = None

    def _compile_sparse_grad_fn(self):
        from .comm.sparse import sparse_embedding_allreduce
        mesh = self.mesh

        @functools.partial(jax.jit, static_argnames=("gas",),
                           out_shardings=(None, self._replicated))
        def sparse_grads(params, batch, gas):
            flat_p, treedef = jax.tree.flatten(params)
            # locate the embedding-table leaf by path
            paths = [jax.tree_util.keystr(kp) for kp, _ in
                     jax.tree_util.tree_flatten_with_path(params)[0]]
            tok_idx = next(i for i, p in enumerate(paths)
                           if "embed" in p and "tok" in p)
            batch_specs = jax.tree.map(lambda _: P(None, "data"), batch)

            def body(params_, batch_local):
                def micro(carry, mb):
                    acc, ls = carry
                    loss, g = jax.value_and_grad(self.model.loss)(params_, mb)
                    return (jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), acc, g),
                            ls + loss), None

                acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                    params_)
                (acc, loss_sum), _ = jax.lax.scan(
                    micro, (acc0, jnp.zeros((), jnp.float32)), batch_local)
                flat_g = treedef.flatten_up_to(acc)
                ids = batch_local["input_ids"]
                out = [sparse_embedding_allreduce(g, ids, "data")
                       if i == tok_idx else jax.lax.psum(g, "data")
                       for i, g in enumerate(flat_g)]
                return treedef.unflatten(out), jax.lax.pmean(loss_sum, "data")

            fn = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), batch_specs), out_specs=(P(), P()),
                axis_names={"data"}, check_vma=False)
            grads, loss_sum = fn(params, batch)
            return grads, loss_sum / gas

        return sparse_grads

    def _sparse_grads_train_batch(self, batch):
        if self._sparse_grad_fn is None:
            self._sparse_grad_fn = self._compile_sparse_grad_fn()
        gas = self.gradient_accumulation_steps()
        batch = jax.tree.map(self._stage_leaf, batch)
        self.tput_timer.start()
        lr = self._next_lr_device()
        self._swap_in_opt_state()
        dp = groups.get_data_parallel_world_size()
        grads, loss = self._sparse_grad_fn(self.module_params, batch, gas=gas)
        # grads are SUMS over ranks and microbatches: divide like the fused
        # step (dp enters because the manual psum sums rather than means)
        (self.module_params, self.opt_state, self.scaler_state, overflow,
         grad_norm) = self._update_fn(self.module_params, self.opt_state,
                                      self.scaler_state, grads, lr,
                                      float(gas * dp))
        self._swap_out_opt_state()
        self.micro_steps += gas
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._post_step(overflow, grad_norm, loss)
        self.tput_timer.stop(global_step=True)
        return loss

    def _prepare_onebit(self):
        """Set up the COMPRESSED-communication stage of the 1-bit optimizers
        (reference ``runtime/fp16/onebit/adam.py:14``): after ``freeze_step``,
        gradients are never reduced at full precision — each rank updates a
        LOCAL momentum from its local gradients and the momentum travels
        through the error-feedback 1-bit allreduce
        (``runtime/comm/compressed.py``), variance frozen. Warmup steps use
        the exact-Adam compiled path."""
        if self.zero_stage != 0:
            raise NotImplementedError(
                "1-bit optimizers are incompatible with ZeRO sharding "
                "(reference constraint): set zero_optimization.stage=0")
        if self._config.fp16.enabled:
            raise NotImplementedError("1-bit compressed stage requires bf16/fp32")
        # the compressed exchange is manual over `data` only; tensor-sharded
        # params/grads ride through the region auto-partitioned (the same
        # partial-manual composition the ZeRO++ step uses), so TP composes.
        # pipe/seq/expert reshape the step itself (schedules, all-to-alls)
        # and stay excluded, as in the reference's DP-group-only exchange.
        for ax in ("pipe", "seq", "expert", "zrep"):
            if self.mesh.shape.get(ax, 1) > 1:
                raise NotImplementedError(
                    f"1-bit compressed comm supports data x tensor meshes "
                    f"(got {ax}>1)")
        self._onebit_freeze_step = int(self.optimizer.hyper.get("freeze_step", 100_000))
        self._onebit_errors = None
        self._onebit_fn = None

    def _init_onebit_errors(self):
        n = self.mesh.shape["data"]
        spec_w = {}

        def alloc(p):
            chunk = (int(np.prod(p.shape)) + n - 1) // n
            return {"worker": jnp.zeros((n,) + tuple(p.shape), jnp.float32),
                    "server": jnp.zeros((n, chunk), jnp.float32)}

        errors = jax.tree.map(alloc, self.module_params)
        sh = NamedSharding(self.mesh, P("data"))
        return jax.device_put(errors, jax.tree.map(
            lambda _: sh, errors, is_leaf=lambda x: isinstance(x, jnp.ndarray)))

    def _compile_onebit_compressed_fn(self):
        from .comm.compressed import compressed_allreduce_body
        hyper = self.optimizer.hyper
        b1, _b2 = hyper["betas"]
        eps = float(hyper["eps"])
        wd = float(hyper.get("weight_decay", 0.0))
        mesh = self.mesh

        @functools.partial(
            jax.jit, donate_argnums=(0, 1, 2), static_argnames=("gas",),
            out_shardings=(self.param_shardings, self.opt_state_shardings,
                           None, self._replicated))
        def comp_step(params, opt_state, errors, batch, lr, gas):
            flat_p, treedef = jax.tree.flatten(params)
            flat_m = treedef.flatten_up_to(
                jax.tree.map(lambda s: s["m"], opt_state["slots"],
                             is_leaf=lambda x: isinstance(x, dict) and "m" in x))
            flat_err = treedef.flatten_up_to(errors)
            step = opt_state["step"] + 1

            batch_specs = jax.tree.map(lambda _: P(None, "data"), batch)
            err_specs = treedef.unflatten([{"worker": P("data"), "server": P("data")}
                                           for _ in flat_p])

            def body(params_, ms, errs, batch_local, lr_, step_):
                def micro(carry, mb):
                    acc, ls = carry
                    loss, g = jax.value_and_grad(self.model.loss)(params_, mb)
                    return (jax.tree.map(jnp.add, acc,
                                         jax.tree.map(lambda x: x.astype(jnp.float32), g)),
                            ls + loss), None

                acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params_)
                (acc, loss_sum), _ = jax.lax.scan(
                    micro, (acc0, jnp.zeros((), jnp.float32)), batch_local)
                g_local = jax.tree.map(lambda g: g / gas, acc)
                flat_g = treedef.flatten_up_to(g_local)
                flat_e = treedef.flatten_up_to(errs)

                new_m, new_err = [], []
                n = jax.lax.axis_size("data")
                for m, g, e in zip(ms, flat_g, flat_e):
                    m_local = b1 * m + (1 - b1) * g
                    m_sum, we, se = compressed_allreduce_body(
                        m_local, e["worker"][0], e["server"][0], "data")
                    new_m.append(m_sum / n)   # compressed allreduce sums
                    new_err.append({"worker": we[None], "server": se[None]})
                return (new_m, treedef.unflatten(new_err),
                        jax.lax.pmean(loss_sum / gas, "data"))

            fn = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), [P()] * len(flat_m), err_specs, batch_specs, P(), P()),
                out_specs=([P()] * len(flat_m), err_specs, P()),
                axis_names={"data"}, check_vma=False)
            new_m, new_errors, loss = fn(params, flat_m, errors, batch,
                                         lr, step.astype(jnp.float32))

            # Adam update with compressed momentum, frozen variance
            # (reference onebit/adam.py compressed stage)
            flat_v = treedef.flatten_up_to(
                jax.tree.map(lambda s: s["v"], opt_state["slots"],
                             is_leaf=lambda x: isinstance(x, dict) and "m" in x))
            new_p = []
            for p, m, v in zip(flat_p, new_m, flat_v):
                p32 = p.astype(jnp.float32)
                # no bias correction in the compressed stage (reference
                # onebit/adam.py: update = exp_avg / (sqrt(exp_avg_sq)+eps))
                upd = m / (jnp.sqrt(v) + eps)
                if wd:
                    upd = upd + wd * p32
                new_p.append((p32 - lr * upd).astype(p.dtype))

            flat_slots = treedef.flatten_up_to(opt_state["slots"])
            new_slots = []
            for s, m in zip(flat_slots, new_m):
                ns = dict(s)
                ns["m"] = m
                new_slots.append(ns)
            new_state = {"step": step, "slots": treedef.unflatten(new_slots)}
            return treedef.unflatten(new_p), new_state, new_errors, loss

        return comp_step

    def _onebit_compressed_train_batch(self, batch):
        if self._onebit_errors is None:
            self._onebit_errors = self._init_onebit_errors()
            log_dist(f"1-bit {self.optimizer.name}: entering COMPRESSED stage at "
                     f"step {self.global_steps + 1}", ranks=[0])
        if self._onebit_fn is None:
            self._onebit_fn = self._compile_onebit_compressed_fn()
        gas = self.gradient_accumulation_steps()
        batch = jax.tree.map(self._stage_leaf, batch)
        self.tput_timer.start()
        lr = self._next_lr_device()
        (self.module_params, self.opt_state, self._onebit_errors,
         loss) = self._onebit_fn(self.module_params, self.opt_state,
                                 self._onebit_errors, batch, lr, gas=gas)
        self.micro_steps += gas
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._post_step(jnp.zeros((), jnp.bool_), None, loss)
        self.tput_timer.stop(global_step=True)
        return loss

    def _compile_host_offload_step_fns(self):
        """Device side of the native ZeRO-Offload step: accumulate fp32
        grads (+ their global norm-squared, so clipping costs no extra host
        pass) on the accelerator; the update happens on host."""

        @functools.partial(
            jax.jit, static_argnames=("gas",),
            # grads leave the step in the OPTIMIZER layout: the host update
            # reads exactly the local shard, never a replicated fetch
            out_shardings=(self._replicated, self._opt_param_shardings,
                           self._replicated))
        def grad_accum_fn(params, batch, scale, gas):
            if gas == 1:
                mb = jax.tree.map(lambda x: x[0], batch)
                loss_sum, acc = self._loss_and_grads(params, batch=mb, scale=scale)
                acc = jax.tree.map(lambda g: g.astype(jnp.float32), acc)
            else:
                def micro(carry, mb):
                    a, ls = carry
                    loss, grads = self._loss_and_grads(params, batch=mb, scale=scale)
                    return (_tree_add(a, grads), ls + loss), None

                acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
                acc0 = jax.tree.map(lambda g, s: jax.lax.with_sharding_constraint(g, s),
                                    acc0, self._grad_inner_shardings)
                (acc, loss_sum), _ = jax.lax.scan(
                    micro, (acc0, jnp.zeros((), jnp.float32)), batch)
            gsq = sum(jnp.vdot(g, g).astype(jnp.float32) for g in jax.tree.leaves(acc))
            return loss_sum / gas, acc, gsq

        self._grad_accum_fn = grad_accum_fn
        if self._twinflow is not None:
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def twinflow_dev_update(params_dev, opt_dev, grads_dev, lr, scale_inv):
                g = jax.tree.map(lambda x: x * scale_inv, grads_dev)
                return self.optimizer.apply(g, opt_dev, params_dev, lr=lr)

            self._twinflow_update_fn = twinflow_dev_update
        self._train_step_fn = None
        self._grad_fn = None
        self._update_fn = None

    def _host_offload_train_batch(self, batch):
        """Native ZeRO-Offload step: device grads → host CPUAdam → re-staged
        params. Overflow handling and dynamic loss scaling match the
        compiled path (skip update, shrink scale)."""
        import numpy as np
        gas = self.gradient_accumulation_steps()
        batch = jax.tree.map(self._stage_leaf, batch)
        self.tput_timer.start()
        scale_dev = self.scaler_state.scale
        loss, acc, gsq = self._grad_accum_fn(self.module_params, batch,
                                             scale_dev, gas=gas)
        tf = self._twinflow
        mask = tf["mask"] if tf is not None else None
        for i, x in enumerate(jax.tree.leaves(acc)):
            if mask is None or mask[i]:   # only host-bound grads cross over
                x.copy_to_host_async()
        gsq_f = float(gsq)
        scale = float(jax.device_get(scale_dev))
        divisor = scale * gas
        overflow = not np.isfinite(gsq_f)
        self.scaler_state = self.loss_scaler.update(self.scaler_state,
                                                    jnp.asarray(overflow))
        grad_norm = float("nan")
        if not overflow:
            lr = float(self._next_lr())
            unscaled_gsq = gsq_f / (divisor * divisor)
            grad_norm = unscaled_gsq ** 0.5
            if tf is None:
                new_params = self._host_optimizer.step(
                    acc, grad_divisor=divisor, lr=lr,
                    grad_norm_sq=unscaled_gsq)
                self.module_params = self._to_param_layout(new_params)
            else:
                treedef = tf["treedef"]
                flat_g = jax.tree.leaves(acc)
                flat_p = jax.tree.leaves(self.module_params)
                host_g = treedef.unflatten(
                    [g if m else None for g, m in zip(flat_g, mask)])
                # device half first — it runs async while CPUAdam works
                scale_inv = 1.0 / divisor
                clip = float(self._config.gradient_clipping or 0.0)
                if clip > 0.0:   # same factor HostOffloadOptimizer derives
                    scale_inv *= min(1.0, clip / (grad_norm + 1e-6))
                dev_p = treedef.unflatten(
                    [p if not m else None for p, m in zip(flat_p, mask)])
                dev_g = treedef.unflatten(
                    [g if not m else None for g, m in zip(flat_g, mask)])
                new_dev_p, tf["dev_state"] = self._twinflow_update_fn(
                    dev_p, tf["dev_state"], dev_g, jnp.float32(lr),
                    jnp.float32(scale_inv))
                new_host = self._to_param_layout(self._host_optimizer.step(
                    host_g, grad_divisor=divisor, lr=lr,
                    grad_norm_sq=unscaled_gsq))
                host_it = iter(jax.tree.leaves(new_host))
                dev_it = iter(jax.tree.leaves(new_dev_p))
                flat_new = [next(host_it) if m else next(dev_it)
                            for m in mask]
                self.module_params = treedef.unflatten(flat_new)
        self._last_grad_norm = grad_norm
        self.micro_steps += gas
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._post_step(jnp.asarray(overflow), jnp.asarray(grad_norm), loss)
        self.tput_timer.stop(global_step=True)
        return loss

    def _compile_pipeline_step_fns(self):
        """Pipeline-parallel step: the gas microbatches feed the pipe ring
        (reference PipelineEngine.train_batch:337); forward/backward are
        fused — the decomposed API raises, as in the reference (engine.py:61
        PipelineEngine forbids separate forward/backward).

        Schedule selection (config ``pipeline.schedule``): "1f1b"/"1f1b-eager"
        run the compiled TrainSchedule engine (explicit vjp backward, bounded
        activation buffers, any model implementing the three-segment
        protocol); "gpipe" keeps the autodiff fill-drain path (CausalLM
        only)."""
        from ..models.transformer import CausalLM
        from .pipe.engine import (build_pipeline_1f1b, build_pipeline_loss,
                                  _pipeline_interface)
        pcfg = self._config.pipeline
        use_1f1b = pcfg.schedule in ("1f1b", "1f1b-eager")
        if use_1f1b:
            _pipeline_interface(self.model)   # raises early if unsupported
            pstep = build_pipeline_1f1b(self.model, self.pipe_parallel_size,
                                        eager=(pcfg.schedule == "1f1b-eager"),
                                        remat=pcfg.remat)
            # Two-phase on purpose: XLA's SPMD partitioner CHECK-fails when
            # one program contains the partial-manual pipe region AND the
            # reshard of its mixed-residue grads (pipe-sharded layer grads +
            # pipe-replicated embed/head grads) into the param/opt layouts.
            # A jit boundary makes the reshard a plain runtime transfer.
            grad_fn = jax.jit(pstep)

            @functools.partial(
                jax.jit,
                donate_argnums=(0, 1, 2),
                out_shardings=(self.param_shardings, self.opt_state_shardings, None,
                               self._replicated, self._replicated))
            def pipe_update_fn(params, opt_state, scaler_state, grads, lr):
                return self._apply_update(params, opt_state, scaler_state,
                                          grads, lr, jnp.float32(1.0))

            def train_step_fn(params, opt_state, scaler_state, batch, lr, gas):
                scale = scaler_state.scale
                loss, grads = grad_fn(params, batch, scale)
                new_params, new_opt, new_scaler, overflow, grad_norm = pipe_update_fn(
                    params, opt_state, scaler_state, grads, lr)
                return new_params, new_opt, new_scaler, loss, overflow, grad_norm

            self._train_step_fn = train_step_fn
            self._grad_fn = grad_fn
            self._update_fn = pipe_update_fn
            return

        assert isinstance(self.model, CausalLM), \
            "gpipe schedule requires a native CausalLM model"
        ploss = build_pipeline_loss(self.model, self.pipe_parallel_size)

        @functools.partial(
            jax.jit,
            donate_argnums=(0, 1, 2),
            static_argnames=("gas",),
            out_shardings=(self.param_shardings, self.opt_state_shardings, None,
                           self._replicated, self._replicated, self._replicated))
        def train_step_fn(params, opt_state, scaler_state, batch, lr, gas):
            scale = scaler_state.scale

            def scaled(p):
                return ploss(p, batch) * scale

            loss, grads = jax.value_and_grad(scaled)(params)
            grads = jax.tree.map(lambda g, s: jax.lax.with_sharding_constraint(g, s),
                                 grads, self.grad_shardings)
            new_params, new_opt, new_scaler, overflow, grad_norm = self._apply_update(
                params, opt_state, scaler_state, grads, lr, jnp.float32(1.0))
            return new_params, new_opt, new_scaler, loss / scale, overflow, grad_norm

        self._train_step_fn = train_step_fn
        self._grad_fn = None
        self._update_fn = None

    def _assert_not_pipeline(self, api):
        if getattr(self, "pipe_parallel_size", 1) > 1:
            raise RuntimeError(f"{api}() is not supported with pipeline parallelism; "
                               "use train_batch() (reference PipelineEngine semantics)")

    # ------------------------------------------------------------------
    # public API (reference parity)
    # ------------------------------------------------------------------

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def train_batch_size(self):
        return self._config.train_batch_size

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def get_lr(self):
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "get_lr"):
            return self.lr_scheduler.get_lr()
        return [self.optimizer.hyper.get("lr", 0.0)]

    def set_lr(self, lr):
        """Override the optimizer lr (reference ``engine.py set_lr``); with a
        scheduler attached the scheduler keeps authority, as in the
        reference."""
        if self.optimizer is not None:
            self.optimizer.hyper["lr"] = float(lr)
        if self._infinity is not None:
            self._infinity.adam.lr = float(lr)
        # _next_lr_device's cache is value-keyed; no invalidation needed

    # -- dynamic batch sizing (reference engine.py set_train_batch_size:
    #    only the accumulation depth changes; the per-chip microbatch and
    #    therefore the compiled step shape stay fixed) --

    def set_train_batch_size(self, train_batch_size: int):
        mbs = self.train_micro_batch_size_per_gpu()
        dp = groups.get_data_parallel_world_size()
        if train_batch_size % (mbs * dp) != 0:
            raise ValueError(
                f"train_batch_size {train_batch_size} must be a multiple of "
                f"micro_batch*dp = {mbs * dp}")
        self._config.gradient_accumulation_steps = train_batch_size // (mbs * dp)
        self._config.train_batch_size = train_batch_size

    def set_train_micro_batch_size(self, micro_batch_size: int):
        """Change the per-chip microbatch; the next train_batch compiles the
        new shape (XLA caches per shape, so alternating sizes is cheap
        after first compile)."""
        gas = self.gradient_accumulation_steps()
        dp = groups.get_data_parallel_world_size()
        self._config.train_micro_batch_size_per_gpu = int(micro_batch_size)
        self._config.train_batch_size = int(micro_batch_size) * gas * dp

    def set_gradient_accumulation_steps(self, gas: int):
        mbs = self.train_micro_batch_size_per_gpu()
        dp = groups.get_data_parallel_world_size()
        self._config.gradient_accumulation_steps = int(gas)
        self._config.train_batch_size = mbs * int(gas) * dp

    def zero_grad(self):
        """No-op for API parity: gradients are functional values produced
        inside the compiled step, never accumulated module state."""

    def load_module_state_dict(self, state_dict, strict: bool = True):
        """Load a (native-layout) param pytree onto the engine's shardings,
        re-seeding any fp32 master copies (host offload / bf16 masters) so
        the next update starts from the loaded weights rather than the
        stale masters."""
        if self._infinity is not None:
            raise NotImplementedError(
                "ZeRO-Infinity streams params from its host/NVMe store; "
                "load weights through load_checkpoint")
        if strict:
            ref = jax.tree.structure(self.module_params)
            got = jax.tree.structure(state_dict)
            if ref != got:
                raise ValueError(
                    f"state_dict tree mismatch: expected {ref}, got {got}")
        self.module_params = jax.device_put(state_dict, self.param_shardings)
        self._resync_masters_from_params()

    def _restore_host_optimizer_state(self, opt_tree, twinflow_dev_tree=None):
        """Route a saved optimizer tree ({"step", "slots"}) into the host
        optimizer (+ the Twin-Flow device half), then derive module params
        from the restored masters — every future host update starts from the
        masters, so module params must track them. Shared by load_checkpoint
        and the universal-checkpoint restore (elastic rejoin)."""
        self._host_optimizer.load_state_dict(opt_tree)
        if self._twinflow is not None:
            if twinflow_dev_tree is not None:
                self._twinflow["dev_state"] = twinflow_dev_tree
            # host masters overwrite only the host-owned leaves; the device
            # half came in with the module section
            tdef, mask = self._twinflow["treedef"], self._twinflow["mask"]
            flat_p = jax.tree.leaves(self.module_params)
            host_half = self._to_param_layout(self._host_optimizer.params())
            host_it = iter(jax.tree.leaves(host_half))
            self.module_params = tdef.unflatten(
                [next(host_it) if m else p for p, m in zip(flat_p, mask)])
        else:
            self.module_params = self._to_param_layout(
                self._host_optimizer.params())

    def _resync_masters_from_params(self):
        """fp32 masters (host offload, Twin-Flow halves, device master
        slots) must track externally loaded module weights."""
        def upd_slots(slots_tree, params_tree):
            return jax.tree.map(
                lambda s, p: ({**s, "master": p.astype(jnp.float32)}
                              if "master" in s else s),
                slots_tree, params_tree,
                is_leaf=lambda x: isinstance(x, dict) and ("m" in x or "master" in x))

        if self._host_optimizer is not None:
            host = self._to_opt_layout(self.module_params)
            if self._twinflow is not None:
                tdef, mask = self._twinflow["treedef"], self._twinflow["mask"]
                flat = jax.tree.leaves(host)
                host = tdef.unflatten(
                    [p if m else None for p, m in zip(flat, mask)])
                dev_params = self._twinflow["treedef"].unflatten(
                    [p if not m else None
                     for p, m in zip(jax.tree.leaves(self.module_params), mask)])
                st = self._twinflow["dev_state"]
                st["slots"] = upd_slots(st["slots"], dev_params)
            self._host_optimizer.reset_masters(host)
        elif isinstance(self.opt_state, dict) and "slots" in self.opt_state:
            self._swap_in_opt_state()
            self.opt_state = {**self.opt_state,
                              "slots": upd_slots(self.opt_state["slots"],
                                                 self.module_params)}

    def save_16bit_model(self, save_dir, save_filename="pytorch_model.bin",
                         exclude_frozen_parameters=False):
        """Consolidate the (possibly ZeRO-sharded) params to one
        low-precision torch-format state dict (reference
        ``engine.py:3607``): keys are dotted native paths, values torch
        tensors in the training dtype (bf16/fp16 when enabled)."""
        import torch

        if self._infinity is not None:
            raise NotImplementedError(
                "ZeRO-Infinity streams params from its host/NVMe store; "
                "consolidate through save_checkpoint + zero_to_fp32")
        dt = self.model.cfg.act_dtype if hasattr(self.model, "cfg") else None
        host = jax.device_get(self.module_params)   # gathers ZeRO shards

        flat = {}

        def walk(prefix, node):
            if isinstance(node, dict):
                for k, v in node.items():
                    walk(f"{prefix}.{k}" if prefix else k, v)
            else:
                a = np.asarray(node)
                if a.dtype.name == "bfloat16":   # torch can't read ml_dtypes
                    t = torch.from_numpy(
                        a.astype(np.float32)).to(torch.bfloat16)
                elif dt is not None and a.dtype == np.float32 and dt != jnp.float32:
                    t = torch.from_numpy(a).to(
                        torch.bfloat16 if dt == jnp.bfloat16 else torch.float16)
                else:
                    t = torch.from_numpy(np.ascontiguousarray(a))
                flat[prefix] = t

        walk("", host)
        os.makedirs(save_dir, exist_ok=True)
        path = os.path.join(save_dir, save_filename)
        torch.save(flat, path)
        log_dist(f"save_16bit_model: {len(flat)} tensors → {path}", ranks=[0])
        return path

    def _current_lr(self):
        return float(self.get_lr()[0])

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def _put_batch(self, batch):
        """Device-put a host batch with batch-dim sharding."""
        def put(x):
            arr = jnp.asarray(x)
            spec = shd.batch_spec(self.mesh)
            nd_spec = P(*list(spec)[:arr.ndim])
            return jax.device_put(arr, NamedSharding(self.mesh, nd_spec))
        return jax.tree.map(put, batch)

    def forward(self, batch=None, **kwargs):
        """Compute loss (and cache grads for the paired backward)."""
        if batch is None:
            batch = kwargs
        self._assert_not_pipeline("forward")
        self.timers(FORWARD_GLOBAL_TIMER).start()
        batch = self._put_batch(batch)
        loss, grads = self._grad_fn(self.module_params, batch, self.scaler_state.scale)
        self._cached = (loss, grads)
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients=True, retain_graph=False):
        """Accumulate the cached microbatch gradients."""
        assert self._cached is not None, "backward() without a preceding forward()"
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        _, grads = self._cached
        self._cached = None
        if self._acc_grads is None:
            self._acc_grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            self._acc_grads = _tree_add(self._acc_grads, grads)
        self._acc_count += 1
        self.micro_steps += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    def step(self, lr_kwargs=None):
        """Apply the optimizer update at a gradient-accumulation boundary."""
        if self.micro_steps % self.gradient_accumulation_steps() != 0:
            return  # not at boundary yet (reference skips inside backward loop)
        assert self._acc_grads is not None, "step() without accumulated gradients"
        self.timers(STEP_GLOBAL_TIMER).start()
        lr = self._next_lr_device()
        self._swap_in_opt_state()
        (self.module_params, self.opt_state, self.scaler_state, overflow,
         grad_norm) = self._update_fn(self.module_params, self.opt_state, self.scaler_state,
                                      self._acc_grads, lr, jnp.float32(self._acc_count))
        self._swap_out_opt_state()
        self._acc_grads = None
        self._acc_count = 0
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._post_step(overflow, grad_norm)
        self.timers(STEP_GLOBAL_TIMER).stop()

    def _stage_leaf(self, x):
        """Reshape one batch leaf to (gas, global_micro, ...) and device-put
        it with batch-dim sharding. Already-staged ``jax.Array`` leaves with
        the right layout pass through without a copy."""
        gas = self.gradient_accumulation_steps()
        mb = self.train_micro_batch_size_per_gpu()
        arr = x if isinstance(x, jax.Array) else jnp.asarray(x)
        if arr.ndim >= 1 and arr.shape[0] == gas * mb * self.dp_world_size:
            arr = arr.reshape((gas, mb * self.dp_world_size) + arr.shape[1:])
        elif arr.ndim >= 2 and arr.shape[0] == gas:
            pass
        else:
            raise ValueError(
                f"train_batch leaf has leading dim {arr.shape[0]}; expected "
                f"gas*global_micro={gas * mb * self.dp_world_size} or (gas, ...) layout")
        spec = shd.batch_spec(self.mesh)
        nd_spec = P(None, *list(spec)[:arr.ndim - 1])
        return jax.device_put(arr, NamedSharding(self.mesh, nd_spec))

    def stage_batch(self, batch):
        """Pre-stage a host batch on device in ``train_batch`` layout.

        Staged batches make the train loop fully async: ``train_batch``
        recognises them and skips host→device transfer (the analog of the
        reference's pinned-buffer ``_exec_load_micro_batch``,
        ``runtime/pipe/engine.py:882``)."""
        return jax.tree.map(self._stage_leaf, batch)

    def train_batch(self, batch):
        """Fused fast path: one compiled step for a full global batch.

        ``batch`` leaves: (gas * micro_bs, ...) or (gas, micro_bs, ...).

        On the profiler's clock the call is a ``train_batch`` step
        (``StepTraceAnnotation``) holding ``train/stage`` (the batch's
        host->device staging) and ``train/dispatch`` (the step program's
        enqueue). Always on: with no profiler attached a ``TraceMe`` is one
        flag test.
        """
        with jax.profiler.StepTraceAnnotation("train_batch",
                                              step_num=self.global_steps):
            return self._train_batch(batch)

    def _train_batch(self, batch):
        if self._infinity is not None:
            gas = self.gradient_accumulation_steps()
            self.tput_timer.start()
            scale = float(jax.device_get(self.scaler_state.scale))
            loss, overflow = self._infinity.train_batch(
                batch, lr=float(self._next_lr()), gas=gas, loss_scale=scale)
            self.scaler_state = self.loss_scaler.update(
                self.scaler_state, jnp.asarray(overflow))
            if overflow:
                self.skipped_steps += 1
            self.micro_steps += gas
            self.global_steps += 1
            self.global_samples += self.train_batch_size()
            self.tput_timer.stop(global_step=True)
            return loss
        if self._host_optimizer is not None:
            return self._host_offload_train_batch(batch)
        if getattr(self, "_onebit", False) and \
                self.global_steps + 1 > self._onebit_freeze_step:
            return self._onebit_compressed_train_batch(batch)
        if getattr(self, "_sparse_grads", False):
            return self._sparse_grads_train_batch(batch)
        gas = self.gradient_accumulation_steps()
        with jax.profiler.TraceAnnotation("train/stage"):
            batch = jax.tree.map(self._stage_leaf, batch)
        self.tput_timer.start()
        lr = self._next_lr_device()
        self._swap_in_opt_state()
        with jax.profiler.TraceAnnotation("train/dispatch"):
            (self.module_params, self.opt_state, self.scaler_state, loss,
             overflow, grad_norm) = self._train_step_fn(
                 self.module_params, self.opt_state, self.scaler_state, batch,
                 lr, gas=gas)
        self._swap_out_opt_state()
        self.micro_steps += gas
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._post_step(overflow, grad_norm, loss)
        self.tput_timer.stop(global_step=True)
        return loss

    def eval_batch(self, batch):
        if self._eval_fn is None:
            self._eval_fn = jax.jit(self.model.loss)
        batch = self._put_batch(batch)
        return self._eval_fn(self.module_params, batch)

    def _swap_in_opt_state(self):
        if self._opt_swapper is not None and self.opt_state is None:
            host_state = self._opt_swapper.swap_in_optimizer()
            self.opt_state = jax.device_put(host_state, self.opt_state_shardings)

    def _swap_out_opt_state(self):
        if self._opt_swapper is not None and self.opt_state is not None:
            self._opt_swapper.swap_out_optimizer(jax.device_get(self.opt_state))
            self.opt_state = None

    def _next_lr(self):
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "step"):
            self.lr_scheduler.step()
            return self.lr_scheduler.get_lr()[0]
        if self.optimizer is not None:
            return self.optimizer.hyper.get("lr", 1e-3)
        if self._infinity is not None:
            return self._infinity.adam.lr
        return 1e-3

    def _next_lr_device(self):
        """Device scalar for the next step's lr, cached while unchanged
        (a fresh host→device scalar transfer every step is one more
        dispatch ahead of the step program)."""
        lr = float(self._next_lr())
        cached = getattr(self, "_lr_cache", None)
        if cached is None or cached[0] != lr:
            self._lr_cache = (lr, jnp.float32(lr))
        return self._lr_cache[1]

    def check_sharded_equivalence(self, batch, rtol=2e-3, atol=2e-4):
        """Debug-mode correctness guard (SURVEY §5 plan; the reference's
        analog is ZeRO's ``safe_mode`` recompute-and-compare,
        ``stage3.py:1282``): compute loss+grads once through the production
        sharded program and once fully replicated on device 0, and assert
        they agree. Catches sharding-rule bugs (a wrong spec that silently
        drops or double-counts a reduction) that loss curves hide.

        Returns (max_abs_err, max_rel_err) on success; raises AssertionError
        with the offending leaf path on mismatch.
        """
        self._assert_not_pipeline("check_sharded_equivalence")
        mb = jax.tree.map(
            lambda x: jnp.asarray(x)[: self.train_micro_batch_size_per_gpu()
                                     * self.dp_world_size], batch)
        scale = jnp.float32(1.0)
        sharded_loss, sharded_grads = self._grad_fn(self.module_params, mb, scale)
        rep_params = jax.device_put(jax.device_get(self.module_params))

        @jax.jit
        def replicated(params, b):
            return jax.value_and_grad(self.model.loss)(params, b)

        ref_loss, ref_grads = replicated(rep_params, jax.device_get(mb))
        np_ = np
        max_abs = max_rel = 0.0
        assert np_.allclose(float(sharded_loss), float(ref_loss),
                            rtol=rtol, atol=atol), \
            f"loss mismatch: sharded={float(sharded_loss)} replicated={float(ref_loss)}"
        flat_s = jax.tree.leaves_with_path(sharded_grads)
        flat_r = jax.tree.leaves(ref_grads)
        for (path, gs), gr in zip(flat_s, flat_r):
            a = np_.asarray(jax.device_get(gs), np_.float32)
            b = np_.asarray(jax.device_get(gr), np_.float32)
            err = np_.abs(a - b)
            rel = err / (np_.abs(b) + 1e-8)
            max_abs = max(max_abs, float(err.max()))
            max_rel = max(max_rel, float(rel.max()))
            if not np_.allclose(a, b, rtol=rtol, atol=atol):
                worst = float(err.max())
                raise AssertionError(
                    f"sharded/replicated grad mismatch at {jax.tree_util.keystr(path)}: "
                    f"max|Δ|={worst:.3e} (rtol={rtol}, atol={atol})")
        log_dist(f"check_sharded_equivalence OK: max|Δ|={max_abs:.2e}", ranks=[0])
        return max_abs, max_rel

    def _post_step(self, overflow, grad_norm, loss=None):
        """Bookkeeping at the gradient-update boundary.

        Device scalars are queued WITHOUT forcing a sync (a per-step fence
        would serialize host and device on remote platforms); once per
        ``steps_per_print`` window everything is fetched at once and fanned
        out to the monitor — loss/lr/loss-scale/grad-norm/throughput, the
        samples the reference engine writes (``engine.py:2001,2222``) — and
        the rank-0 progress log."""
        self._pending_overflow.append(overflow)
        spp = max(1, int(self._config.steps_per_print or 10 ** 9))
        if self.global_steps % spp != 0:
            return
        n_over = sum(int(jax.device_get(o)) for o in self._pending_overflow)
        self._pending_overflow.clear()
        self.skipped_steps += n_over
        scale = float(jax.device_get(self.scaler_state.scale)) \
            if self.scaler_state is not None else 1.0
        gnorm = float(jax.device_get(grad_norm)) if grad_norm is not None else None
        lval = float(jax.device_get(loss)) if loss is not None else None
        lr = self._current_lr()
        tput = self.tput_timer.avg_samples_per_sec()
        if n_over:
            log_dist(f"step={self.global_steps} {n_over} OVERFLOW step(s) in "
                     f"window, scale -> {scale}", ranks=[0])
        if self.monitor is not None and getattr(self.monitor, "enabled", False):
            step = self.global_steps
            events = [("Train/lr", lr, step),
                      ("Train/loss_scale", scale, step)]
            if lval is not None:
                events.append(("Train/loss", lval, step))
            if gnorm is not None:
                events.append(("Train/grad_norm", gnorm, step))
            if tput > 0:
                events.append(("Train/samples_per_sec", tput, step))
            self.monitor.write_events(events)

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:2763-3607)
    # ------------------------------------------------------------------

    def _ckpt_engine(self):
        if self._checkpoint_engine is None:
            from .checkpoint_engine.orbax_engine import OrbaxCheckpointEngine
            self._checkpoint_engine = OrbaxCheckpointEngine(
                async_save=self._config.checkpoint_config.async_save)
        return self._checkpoint_engine

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True,
                        exclude_frozen_parameters=False):
        tag = tag or f"global_step{self.global_steps}"
        if self._infinity is not None:
            import pickle
            path = os.path.join(save_dir, str(tag))
            os.makedirs(path, exist_ok=True)
            with open(os.path.join(path, "infinity_state.pkl"), "wb") as f:
                pickle.dump({"runner": self._infinity.state_dict(),
                             "meta": {"global_steps": self.global_steps,
                                      "global_samples": self.global_samples,
                                      "micro_steps": self.micro_steps,
                                      "skipped_steps": self.skipped_steps,
                                      "client_state": client_state or {}}}, f)
            if save_latest:
                with open(os.path.join(save_dir, "latest"), "w") as f:
                    f.write(str(tag))
            return True
        self._swap_in_opt_state()
        state = {
            "module": self.module_params,
            # host offload: assemble the sharded host state into global
            # arrays (each process contributes its slices)
            "optimizer": (self._host_optimizer.state_dict()
                          if self._host_optimizer is not None
                          else self.opt_state),
            **({"twinflow_device": self._twinflow["dev_state"]}
               if self._twinflow is not None else {}),
            "scaler": self.scaler_state._asdict(),
            "meta": {
                "global_steps": self.global_steps,
                "global_samples": self.global_samples,
                "micro_steps": self.micro_steps,
                "skipped_steps": self.skipped_steps,
                "lr_scheduler": (self.lr_scheduler.state_dict()
                                 if self.lr_scheduler is not None and
                                 hasattr(self.lr_scheduler, "state_dict") else None),
                "zero_stage": self.zero_stage,
                "client_state": client_state or {},
            },
        }
        self._ckpt_engine().save(state, os.path.join(save_dir, str(tag)))
        if save_latest and jax.process_index() == 0:
            os.makedirs(save_dir, exist_ok=True)
            with open(os.path.join(save_dir, "latest"), "w") as f:
                f.write(str(tag))
        return True

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False):
        if tag is None:
            latest_path = os.path.join(load_dir, "latest")
            if os.path.isfile(latest_path):
                with open(latest_path) as f:
                    tag = f.read().strip()
            else:
                logger.warning(f"No 'latest' file at {load_dir}; nothing loaded")
                return None, {}
        path = os.path.join(load_dir, str(tag))
        if self._infinity is not None:
            import pickle
            with open(os.path.join(path, "infinity_state.pkl"), "rb") as f:
                blob = pickle.load(f)
            self._infinity.load_state_dict(blob["runner"])
            meta = blob["meta"]
            self.global_steps = int(meta["global_steps"])
            self.global_samples = int(meta.get("global_samples", 0))
            self.micro_steps = int(meta.get("micro_steps", 0))
            self.skipped_steps = int(meta.get("skipped_steps", 0))
            return path, meta.get("client_state", {})
        template = {
            "module": (self.module_params, self.param_shardings),
            "optimizer": ((self._host_optimizer.abstract_state_dict(), None)
                          if self._host_optimizer is not None
                          else (self.opt_state, self.opt_state_shardings)),
            **({"twinflow_device": (self._twinflow["dev_state"], None)}
               if self._twinflow is not None else {}),
            "scaler": (self.scaler_state._asdict(), None),
        }
        state = self._ckpt_engine().load(path, template)
        self.module_params = state["module"]
        if load_module_only:
            return path, state["meta"].get("client_state", {})
        if load_optimizer_states:
            if self._host_optimizer is not None:
                self._restore_host_optimizer_state(
                    state["optimizer"],
                    state["twinflow_device"] if self._twinflow is not None
                    else None)
            else:
                self.opt_state = state["optimizer"]
        self.scaler_state = LossScaleState(**{
            k: jax.device_put(jnp.asarray(v), self._replicated)
            for k, v in state["scaler"].items()})
        meta = state["meta"]
        self.global_steps = int(meta["global_steps"])
        self.global_samples = int(meta["global_samples"])
        self.micro_steps = int(meta["micro_steps"])
        self.skipped_steps = int(meta.get("skipped_steps", 0))
        if load_lr_scheduler_states and self.lr_scheduler is not None and \
                meta.get("lr_scheduler") is not None and hasattr(self.lr_scheduler, "load_state_dict"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        return path, meta.get("client_state", {})

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def get_global_grad_norm(self):
        return getattr(self, "_last_grad_norm", None)

    def zero_optimization(self):
        return self.zero_stage > 0

    def zero_optimization_stage(self):
        return self.zero_stage

    @property
    def params(self):
        return self.module_params

    def module_state_dict(self):
        """Full (consolidated) parameter pytree as host numpy arrays —
        analog of ``_zero3_consolidated_16bit_state_dict`` (engine.py:3538)."""
        full = jax.device_get(
            jax.jit(lambda p: p, out_shardings=jax.tree.map(lambda _: self._replicated,
                                                            self.param_shardings,
                                                            is_leaf=lambda x: isinstance(x, NamedSharding))
                    )(self.module_params))
        return full
