"""Logical-axis sharding rules: the TPU-native core of ZeRO and TP.

The reference implements ZeRO by hand-partitioning flat fp32 buffers and
scheduling NCCL collectives (``runtime/zero/stage_1_and_2.py:646`` round-robin
partitioning, ``stage3.py:1282`` reduce-scatter pump). On TPU the same
semantics are expressed declaratively: every parameter carries a tuple of
*logical* axis names; rules map logical axes to mesh axes; XLA's SPMD
partitioner then emits the exact allgather/reduce-scatter schedule the
reference hand-codes:

- ZeRO-0: params/grads/optimizer replicated over ``data``; grads all-reduced.
- ZeRO-1: optimizer state (master weights, moments) additionally sharded over
  the ZeRO axes — the update runs shard-local, then updated params are
  all-gathered (same schedule as ``stage_1_and_2.py`` partition + allgather).
- ZeRO-2: gradients annotated with the optimizer-state sharding, so XLA
  lowers the grad reduction to reduce-scatter instead of all-reduce.
- ZeRO-3: parameters themselves stored sharded; the forward/backward
  allgathers are compiled into the step (prefetching is XLA's latency-hiding
  scheduler doing what ``partitioned_param_coordinator.py`` does by hand).

Tensor parallelism (Megatron-style column/row splits, reference
``module_inject/auto_tp.py``) is the same mechanism: "heads"/"mlp"/"vocab"
logical axes map to the ``tensor`` mesh axis.
"""

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import groups

# Logical axis vocabulary used by deepspeed_tpu.models.
#   batch      – per-example batch dim of activations
#   seq_act    – sequence dim of activations (sharded under sequence parallelism)
#   vocab      – vocabulary dim of embedding / lm head
#   embed      – model (hidden) dim
#   heads      – attention query-head dim
#   kv_heads   – attention kv-head dim (GQA)
#   head_dim   – per-head feature dim
#   mlp        – MLP intermediate dim
#   expert     – expert dim of MoE weights
#   layers     – stacked-layer (scan) dim
#   unmodeled  – never sharded

# (logical_axis, mesh_axis) rules; first match wins. A mesh axis is consumed
# at most once per parameter (XLA requirement).
BASE_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("batch", ("zrep", "data", "expert")),
    ("seq_act", "seq"),
    ("vocab", "tensor"),
    ("heads", "tensor"),
    ("kv_heads", "tensor"),
    ("mlp", "tensor"),
    ("expert", "expert"),
    ("embed", None),
    ("head_dim", None),
    ("layers", "pipe"),   # stage-sharded layer stack when pipeline parallel
    ("unmodeled", None),
)

# ZeRO param/optimizer-state sharding: shard the "embed" logical axis over the
# ZeRO axes (data×expert×seq product). Norm/bias vectors (1D "embed") stay
# replicated — sharding tiny vectors wastes collectives, mirroring the
# reference's round-robin which also keeps small tensors whole
# (stage_1_and_2.py:646 partitions the *flat* buffer; here sharding is
# per-tensor so we skip sub-threshold tensors instead).
FSDP_AXIS = ("data", "expert", "seq")


def zero_rules(stage: int, base=BASE_RULES):
    """Rules for *parameter* sharding at a given ZeRO stage."""
    if stage >= 3:
        return tuple(("embed", FSDP_AXIS) if r[0] == "embed" else r for r in base)
    return base


def optimizer_state_rules(stage: int, base=BASE_RULES, hpz: bool = False):
    """Rules for optimizer-state (master weights/moments) sharding.

    With ``hpz`` (ZeRO++ hierarchical partitioning, reference
    ``groups.py:529`` + ``partition_parameters.py:1653``), optimizer state
    shards over the FULL data-parallel world (zrep × data), while params keep
    the within-group secondary partition — the post-step param refresh is a
    zrep-axis allgather XLA emits from the sharding mismatch."""
    if stage >= 1:
        axes = (("zrep",) + FSDP_AXIS) if hpz else FSDP_AXIS
        return tuple(("embed", axes) if r[0] == "embed" else r for r in base)
    return base


def _first_shardable(logical_axes, mesh, used):
    """Pick the first logical axis to receive the FSDP axes (largest-dim heuristic
    is unnecessary: 'embed' appears in every weight matrix)."""
    return None


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    rules=BASE_RULES,
                    mesh: Optional[Mesh] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec.

    Skips assignments whose mesh axis was already consumed by an earlier dim,
    and drops sharding when the dim size is unknown (callers with shapes should
    use :func:`shard_spec_for`).
    """
    if mesh is None:
        mesh = groups.get_mesh()
    rule_map = {name: ax for name, ax in rules}
    used = set()
    out = []
    for ax in logical_axes:
        mesh_axes = rule_map.get(ax) if ax is not None else None
        if mesh_axes is None:
            out.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        free = tuple(m for m in mesh_axes if m not in used and mesh.shape.get(m, 1) > 1)
        if not free:
            out.append(None)
            continue
        used.update(free)
        out.append(free if len(free) > 1 else free[0])
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard_spec_for(shape: Sequence[int],
                   logical_axes: Sequence[Optional[str]],
                   rules=BASE_RULES,
                   mesh: Optional[Mesh] = None,
                   min_shard_size: int = 2 ** 11) -> P:
    """PartitionSpec for a concrete shape: validates divisibility, skips
    sub-threshold tensors (small vectors stay replicated)."""
    if mesh is None:
        mesh = groups.get_mesh()
    total = 1
    for s in shape:
        total *= int(s)
    if total < min_shard_size:
        return P()
    spec = logical_to_spec(logical_axes, rules, mesh)
    out = []
    for i, part in enumerate(spec):
        if part is None:
            out.append(None)
            continue
        axes = (part,) if isinstance(part, str) else part
        import math
        n = math.prod(mesh.shape[a] for a in axes)
        if shape[i] % n != 0:
            out.append(None)  # not divisible → replicate this dim
        else:
            out.append(part)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_shardings(abstract_params, logical_tree, rules=BASE_RULES, mesh=None):
    """Build a pytree of NamedShardings matching ``abstract_params``.

    ``logical_tree`` mirrors the param tree; each leaf is a tuple of logical
    axis names (len == ndim of the corresponding param).
    """
    if mesh is None:
        mesh = groups.get_mesh()

    def one(p, axes):
        spec = shard_spec_for(p.shape, axes, rules, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree.map(one, abstract_params, logical_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x))


def tree_specs(abstract_params, logical_tree, rules=BASE_RULES, mesh=None,
               min_shard_size: int = 2 ** 11):
    """Like :func:`tree_shardings` but returns raw PartitionSpecs."""
    if mesh is None:
        mesh = groups.get_mesh()

    def one(p, axes):
        return shard_spec_for(p.shape, axes, rules, mesh,
                              min_shard_size=min_shard_size)

    return jax.tree.map(one, abstract_params, logical_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x))


def inference_tp_specs(abstract_params, logical_tree, mesh: Mesh,
                       axis: str = "tp", vocab_sharded: bool = True,
                       rules=BASE_RULES):
    """PartitionSpec tree for tensor-parallel SERVING over a 1-D mesh.

    Same logical-axis vocabulary and rule set as training (BASE_RULES), with
    the ``tensor`` mesh axis rebound to the serving mesh's ``axis`` — the
    Megatron column/row layout falls out of the rules: heads/kv_heads/mlp
    column-sharded, wo/w_out row-sharded (their contraction dim carries the
    same logical axis), vocab-sharded embedding + LM head.

    Differences from the training spec builders, both deliberate:

    - NO min-size threshold. The ``shard_map``-compiled frame loops issue
      manual per-layer collectives whose arithmetic assumes every heads/
      kv_heads/mlp-carrying tensor is actually sharded — a silently
      replicated wq would double-count in the attention-output psum. The
      caller validates divisibility up front
      (``model_implementations.archs.validate_tp_serving``) instead of
      falling back per-tensor.
    - ``vocab_sharded=False`` drops the vocab rule entirely (embedding and
      LM head replicated, no logit all-gather) — the fallback for vocab
      sizes the tp degree doesn't divide, which only costs memory, never
      correctness.
    """
    eff = []
    for la, ma in rules:
        if ma == "tensor":
            ma = axis
        elif isinstance(ma, tuple):
            ma = tuple(axis if m == "tensor" else m for m in ma)
        if la == "vocab" and not vocab_sharded:
            ma = None
        eff.append((la, ma))
    return tree_specs(abstract_params, logical_tree, rules=tuple(eff),
                      mesh=mesh, min_shard_size=0)


def batch_spec(mesh=None) -> P:
    """Sharding of a (batch, seq, ...) activation batch: batch over data-like
    axes, sequence over the seq axis."""
    if mesh is None:
        mesh = groups.get_mesh()
    batch_axes = tuple(a for a in groups.BATCH_AXES if mesh.shape.get(a, 1) > 1)
    seq_axis = "seq" if mesh.shape.get("seq", 1) > 1 else None
    return P(batch_axes if batch_axes else None, seq_axis)


def constrain(x, spec: P, mesh=None):
    """with_sharding_constraint helper usable inside jit."""
    if mesh is None:
        mesh = groups.get_mesh()
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def current_manual_axes():
    """Mesh axes currently in shard_map manual mode at this trace point.

    Sharding constraints must not mention manual axes; layout anchors filter
    through this so model code works both under plain SPMD jit and inside
    partial-auto shard_map regions (e.g. the ZeRO++ quantized-collective
    step)."""
    return set(jax.sharding.get_abstract_mesh().manual_axes)


