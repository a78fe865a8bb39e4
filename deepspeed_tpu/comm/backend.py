"""Communication backends.

Analog of ``deepspeed/comm/backend.py:25`` (Backend ABC) + ``comm/torch.py:90``
(TorchBackend). On TPU the "backend" is XLA itself: collectives are
``jax.lax`` primitives compiled into the step and scheduled onto ICI/DCN by the
runtime, so the backend's job is (a) process bring-up (``jax.distributed``),
(b) exposing eager collectives for host-level control flow (barriers, scalar
consensus, benchmarking) by jitting ``shard_map`` wrappers over the mesh, and
(c) tagging in-trace collectives for the comms logger.
"""

import functools
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import groups
from ..utils.logging import logger


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"
    BAND = "band"
    BOR = "bor"
    BXOR = "bxor"


def _lax_reduce(op, x, axis_name):
    if op == ReduceOp.SUM:
        return jax.lax.psum(x, axis_name)
    if op == ReduceOp.AVG:
        return jax.lax.pmean(x, axis_name)
    if op == ReduceOp.MAX:
        return jax.lax.pmax(x, axis_name)
    if op == ReduceOp.MIN:
        return jax.lax.pmin(x, axis_name)
    if op in (ReduceOp.PROD, ReduceOp.BAND, ReduceOp.BOR, ReduceOp.BXOR):
        # No native XLA collective: gather the n shards (n static) and fold.
        import functools as ft
        gathered = jax.lax.all_gather(x, axis_name, axis=0, tiled=False)
        if op == ReduceOp.PROD:
            return jnp.prod(gathered, axis=0)
        fold = {ReduceOp.BAND: jnp.bitwise_and, ReduceOp.BOR: jnp.bitwise_or,
                ReduceOp.BXOR: jnp.bitwise_xor}[op]
        return ft.reduce(fold, [gathered[i] for i in range(gathered.shape[0])])
    raise ValueError(f"Unsupported reduce op: {op}")


def _normalize_group(group) -> tuple:
    """group may be None (all data-like axes), an axis name, or a tuple of axis names."""
    if group is None:
        return tuple(a for a in groups.MESH_AXIS_ORDER if groups.get_mesh().shape[a] > 1) or ("data",)
    if isinstance(group, str):
        return (group,)
    return tuple(group)


class XlaBackend:
    """Eager collectives over the global mesh, compiled once per (shape, op).

    These exist for host-level control flow and benchmarking; hot-loop
    collectives should live inside the user's jitted step where XLA fuses and
    schedules them.
    """

    name = "xla"

    def __init__(self):
        self._initialized = False
        self._collective_cache = {}

    def init_process_group(self, coordinator_address=None, num_processes=None, process_id=None):
        if self._initialized:
            return
        if num_processes is not None and num_processes > 1:
            # Must run before ANY jax call that touches the XLA backend
            # (callers must not query jax.devices()/process_count() first).
            kw = {}
            hb = os.environ.get("DS_ELASTIC_HEARTBEAT_S")
            if hb:   # elastic bring-up: fast failure detection
                kw["heartbeat_timeout_seconds"] = int(hb)
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                **kw,
            )
        self._initialized = True

    @property
    def initialized(self):
        return self._initialized

    def rank(self):
        return jax.process_index()

    def size(self):
        return jax.process_count()

    def device_count(self):
        return jax.device_count()

    # -- eager collectives (operate on mesh-sharded arrays) --

    def _make_collective(self, kind, axis_names, op, ndim, scatter_dim=0, gather_dim=0):
        mesh = groups.get_mesh()
        key = (mesh, kind, axis_names, op, ndim, scatter_dim, gather_dim)
        cached = self._collective_cache.get(key)
        if cached is not None:
            return cached
        axis = axis_names if len(axis_names) > 1 else axis_names[0]
        full = P(*([None] * ndim))

        if kind == "all_reduce":
            in_spec = out_spec = full

            def fn(x):
                return _lax_reduce(op, x, axis)
        elif kind == "all_gather":
            in_spec = P(axis_names, *([None] * (ndim - 1)))
            out_spec = full

            def fn(x):
                return jax.lax.all_gather(x, axis, axis=0, tiled=True)
        elif kind == "reduce_scatter":
            in_spec = full
            out_spec = P(axis_names, *([None] * (ndim - 1)))

            def fn(x):
                return jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
        elif kind == "all_to_all":
            in_spec = P(axis_names, *([None] * (ndim - 1)))
            out_spec = P(axis_names, *([None] * (ndim - 1)))

            def fn(x):
                return jax.lax.all_to_all(x, axis, split_axis=scatter_dim, concat_axis=gather_dim, tiled=True)
        elif kind == "broadcast":
            in_spec = out_spec = full

            def fn(x):
                # replicate rank-0's copy: select index 0 along the axis
                idx = jax.lax.axis_index(axis)
                return jax.lax.psum(jnp.where(idx == 0, x, jnp.zeros_like(x)), axis)
        else:
            raise ValueError(kind)

        smapped = jax.shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                                out_specs=out_spec, check_vma=False)
        jitted = jax.jit(smapped)
        if len(self._collective_cache) > 512:
            self._collective_cache.clear()
        self._collective_cache[key] = jitted
        return jitted

    def all_reduce(self, tensor, op=ReduceOp.SUM, group=None):
        axes = _normalize_group(group)
        return self._make_collective("all_reduce", axes, op, tensor.ndim)(tensor)

    def all_gather_into_tensor(self, tensor, group=None):
        axes = _normalize_group(group)
        return self._make_collective("all_gather", axes, ReduceOp.SUM, tensor.ndim)(tensor)

    def reduce_scatter_tensor(self, tensor, op=ReduceOp.SUM, group=None):
        axes = _normalize_group(group)
        return self._make_collective("reduce_scatter", axes, op, tensor.ndim)(tensor)

    def all_to_all_single(self, tensor, scatter_dim=0, gather_dim=0, group=None):
        axes = _normalize_group(group)
        return self._make_collective("all_to_all", axes, ReduceOp.SUM, tensor.ndim, scatter_dim,
                                     gather_dim)(tensor)

    def broadcast(self, tensor, src=0, group=None):
        if src != 0:
            raise NotImplementedError("eager broadcast supports src=0 (mesh-major rank) only")
        axes = _normalize_group(group)
        return self._make_collective("broadcast", axes, ReduceOp.SUM, tensor.ndim)(tensor)

    def barrier(self, group=None):
        # A tiny allreduce forces a rendezvous across all participants.
        x = jnp.ones((1,), dtype=jnp.int32)
        jax.block_until_ready(self.all_reduce(x, ReduceOp.SUM, group))

    def destroy_process_group(self):
        self._initialized = False
        self._collective_cache.clear()


# In-trace collective functions — usable inside shard_map'd code. These are the
# hot-path API: thin, traced, fused by XLA.

def psum(x, axis_name):
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name):
    return jax.lax.pmean(x, axis_name)


def pmax(x, axis_name):
    return jax.lax.pmax(x, axis_name)


def all_gather(x, axis_name, axis=0, tiled=True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def psum_scatter(x, axis_name, scatter_dimension=0, tiled=True):
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled)


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)


def ppermute(x, axis_name, perm):
    return jax.lax.ppermute(x, axis_name, perm)


def ring_send_recv(x, axis_name, shift=1):
    """Send to rank+shift, receive from rank-shift along a ring (pipeline p2p analog)."""
    n = jax.lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)
