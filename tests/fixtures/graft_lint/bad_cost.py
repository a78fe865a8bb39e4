"""GL204 fixtures: redundant-collective shapes inside a shard_map manual
region — wire bytes spent on values one collective already computes.

- ``dup_psum``           — the identical operand all-reduced twice on the
  same axis (a refactor that left both the helper's psum and the caller's);
- ``double_reduce``      — a psum applied to a psum's output: the value is
  already replica-invariant, so the second reduce silently multiplies by N;
- ``gather_then_reduce`` — an all-gather whose result is summed straight
  back down ((N-1)x the bytes of the psum computing the same thing — the
  shape the pre-ring quantized all-reduce had);
- ``clean``              — a single psum plus a LEGITIMATE gather (consumed
  whole) that must not trip any of the above.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _mesh():
    return Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("tp",))


def _program(name, fn, out_specs=P()):
    from deepspeed_tpu.analysis.jaxpr_checks import TracedProgram
    mapped = jax.shard_map(fn, mesh=_mesh(), in_specs=P("tp"),
                           out_specs=out_specs, check_vma=False)

    def trace():
        return jax.make_jaxpr(mapped)(jnp.ones((8, 4), jnp.float32))

    return TracedProgram(name=name, trace=trace, retrace=trace)


def dup_psum():
    def body(x):
        a = jax.lax.psum(x, "tp")
        b = jax.lax.psum(x, "tp")     # identical reduce, second wire trip
        return a + b
    return _program("fixture:dup_psum", body)


def double_reduce():
    def body(x):
        y = jax.lax.psum(x, "tp")
        return jax.lax.psum(y, "tp")  # already invariant: multiplies by N
    return _program("fixture:double_reduce", body)


def gather_then_reduce():
    def body(x):
        g = jax.lax.all_gather(x, "tp")          # (tp, ...) per shard
        return jnp.sum(g.astype(jnp.float32), axis=0)
    return _program("fixture:gather_then_reduce", body)


def clean():
    def body(x):
        red = jax.lax.psum(x, "tp")
        g = jax.lax.all_gather(x, "tp")          # consumed whole: fine
        return red + g.reshape(-1)[: x.shape[0] * x.shape[1]].reshape(x.shape)
    return _program("fixture:clean_cost", body)
