"""GL003 fixtures: the three collective-structure failure modes inside a
``shard_map`` manual region.

- ``wrong_axis``   — a psum naming an axis no mesh defines (the classic
  copy-paste from a 2-D training mesh into the 1-D serving mesh);
- ``bad_ring``     — a ppermute whose perm double-delivers to one shard
  (a ring exchange built from it silently loses a chunk);
- ``leaky_output`` — an output DECLARED replicated that actually varies by
  shard (``axis_index`` reaches it with no collective in between). The
  frame loops compile with ``check_vma=False``, so only this static pass
  would catch it.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _mesh():
    return Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("tp",))


def _program(name, fn, out_specs):
    from deepspeed_tpu.analysis.jaxpr_checks import TracedProgram
    mesh = _mesh()
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=P("tp"),
                           out_specs=out_specs, check_vma=False)

    def trace():
        return jax.make_jaxpr(mapped)(jnp.ones((8, 4), jnp.float32))

    return TracedProgram(name=name, trace=trace, retrace=trace)


def wrong_axis():
    def body(x):
        return jax.lax.psum(x, "dp")      # no mesh defines 'dp'
    return _program("fixture:wrong_axis_psum", body, P("tp"))


def bad_ring():
    def body(x):
        perm = [(0, 1), (1, 0), (2, 0)]   # shard 0 receives twice, 2 never
        return jax.lax.ppermute(x, "tp", perm)
    return _program("fixture:bad_ring_ppermute", body, P("tp"))


def leaky_output():
    def body(x):
        # shard-varying value flows to an output declared replicated —
        # each replica silently holds a different "replicated" result
        return jnp.sum(x) + jax.lax.axis_index("tp").astype(jnp.float32)
    return _program("fixture:leaky_replicated_output", body, P())


def clean():
    """The well-formed counterpart: psum makes the output genuinely
    replica-invariant, so the taint pass must stay silent."""
    def body(x):
        return jax.lax.psum(jnp.sum(x), "tp")
    return _program("fixture:clean_psum", body, P())
