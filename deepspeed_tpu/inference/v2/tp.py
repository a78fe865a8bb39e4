"""Tensor-parallel serving context: the mesh + sharding layout the
shard_map-compiled frame loops run under.

The frame loop (``model_runner.frame_loop`` and friends) is one jit whose
carry is the whole serving state. Tensor parallelism keeps that contract and
splits only the MODEL across an explicit 1-D ``tp`` mesh
(DeepSpeed-Inference, arXiv 2207.00032):

- **weights** column/row-sharded per the existing ``parallel/sharding.py``
  logical-axis rules (``inference_tp_specs``): wq/wk/wv over heads,
  wo/w_out over their contraction dim, MLP over the intermediate dim,
  embedding + LM head over vocab when divisible;
- **paged KV pools** (target AND draft) sharded head-wise —
  ``(L, KVH/tp, NB, bs, D)`` per shard, so block tables, the allocator,
  and admission arithmetic are untouched;
- **the slot-table carry** (prompts, limits, cached/produced watermarks,
  stats, poison/nonfinite latches, RNG) fully REPLICATED, so every
  frame-boundary policy — admission, scheduling, deadlines, quarantine,
  preemption, crash snapshot/resume — stays single-host and
  engine-shape-agnostic: a ledger snapshot taken at tp=8 resumes on a
  tp=1 engine and vice versa.

Inside the manual region each step issues explicit collectives
(``parallel/collectives.py``): a psum after the attention output and MLP
output projections, a masked-lookup psum for the vocab-sharded embedding,
and an all-gather for the vocab-sharded logits — with T3-style overlap and
EQuARX-style int8 lowerings behind ``TPCollectives`` flags.
"""

import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...parallel.collectives import TPCollectives
from ...parallel.sharding import inference_tp_specs

TP_AXIS = "tp"


@dataclasses.dataclass(frozen=True)
class TPContext:
    """Everything a runner/slot-table needs to compile under the tp mesh."""

    mesh: Mesh
    degree: int
    coll: TPCollectives
    vocab_sharded: bool
    param_specs: Any          # PartitionSpec pytree mirroring the params
    axis: str = TP_AXIS

    @property
    def kv_spec(self) -> P:
        """Paged KV pools (L, KVH, NB, bs, D): head-wise over tp."""
        return P(None, self.axis)

    def rep(self) -> NamedSharding:
        """Replicated placement for carry/slot-table arrays."""
        return NamedSharding(self.mesh, P())

    def shard_params(self, params):
        """Place a param pytree onto the mesh per ``param_specs``."""
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            params, self.param_specs)


def build_tp_context(model, tp: int, *, quantized: bool = False,
                     overlap: bool = False, payload: str = "int8",
                     role: str = "target",
                     mesh: Optional[Mesh] = None) -> Optional[TPContext]:
    """Build the serving TP context for ``model`` (a ``CausalLM``).

    Validates arch compatibility (``archs.validate_tp_serving``: heads/
    kv_heads/ffn divisibility, no MoE, no head-spanning QK norms), builds a
    1-D ``tp`` mesh over the first ``tp`` local devices (or reuses
    ``mesh`` — the draft shares the target's), and derives the param spec
    tree from the model's ``logical_axes()`` via the shared sharding rules.
    Returns None for ``tp <= 1`` — the tp=1 path must stay byte-identical
    to the unsharded engine, so it never touches shard_map at all."""
    if tp <= 1:
        return None
    from .model_implementations.archs import validate_tp_serving
    validate_tp_serving(model.cfg, tp, role=role)
    if mesh is None:
        devs = jax.devices()
        if len(devs) < tp:
            raise ValueError(
                f"tp={tp} needs {tp} devices, found {len(devs)} "
                "(on CPU, force a virtual mesh with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "before jax initializes)")
        mesh = Mesh(np.asarray(devs[:tp]).reshape(tp), (TP_AXIS,))
    vocab_sharded = model.cfg.vocab_size % tp == 0
    specs = inference_tp_specs(model.abstract_params(), model.logical_axes(),
                               mesh, axis=TP_AXIS,
                               vocab_sharded=vocab_sharded)
    return TPContext(mesh=mesh, degree=tp,
                     coll=TPCollectives(axis=TP_AXIS, degree=tp,
                                        quantized=quantized, overlap=overlap,
                                        payload=payload),
                     vocab_sharded=vocab_sharded, param_specs=specs)
