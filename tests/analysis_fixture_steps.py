"""Known-bad step functions for hvd-analyze's jaxpr checks.

Each ``*_spec`` factory is zero-arg and returns ``(fn, args)`` — the
shape ``analysis.__main__``'s ``--step MOD:ATTR`` and the programmatic
``analyze_step(fn, *args)`` both consume — where ``fn`` exhibits exactly
ONE check's trap.  Lines that must be flagged carry a
``# <- <check-id>`` marker so tests can assert exact file:line without
hard-coding line numbers.

This module only BUILDS traceable functions (args are
``ShapeDtypeStruct`` skeletons); nothing here executes on a device.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _mesh():
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devs, ("dp", "mp"))


def _x():
    return jax.ShapeDtypeStruct((8, 4), jnp.float32)


def cond_psum_spec():
    """A collective inside a cond branch: rank-divergent → deadlock."""
    mesh = _mesh()

    def fn(x):
        def inner(x):
            return lax.cond(
                x.sum() > 0,
                lambda v: lax.psum(v, "dp"),  # <- jax-cond-collective
                lambda v: v,
                x)
        return shard_map(inner, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"), check_vma=False)(x)
    return fn, (_x(),)


def grad_psum_spec():
    """psum INSIDE the differentiated loss under shard_map: the cotangent
    seeds once per device and gradients scale by the axis size."""
    mesh = _mesh()

    def fn(x):
        def inner(x):
            def loss(v):
                return lax.psum((v ** 2).sum(), "dp")  # <- jax-grad-psum
            return jax.grad(loss)(x)
        return shard_map(inner, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"), check_vma=False)(x)
    return fn, (_x(),)


def cond_carry_spec():
    """Optimizer-moment-sized state passed through a cond unchanged: the
    every-k copy trap (moe_opt.every_k's lax.cond form)."""
    moments = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MiB

    def fn(step, m):
        def apply(args):
            s, mm = args
            return s + 1, mm * 0.9

        def skip(args):
            s, mm = args
            return s + 1, mm

        return lax.cond(step % 4 == 0, apply, skip, (step, m))  # <- jax-cond-carry
    return fn, (jax.ShapeDtypeStruct((), jnp.int32), moments)


def bad_axis_spec():
    """Collective over an axis name no mesh binds."""
    mesh = _mesh()

    def fn(x):  # <- jax-unknown-axis  (trace aborts; location is fn itself)
        def inner(x):
            return lax.psum(x, "dpp")  # typo'd axis name
        return shard_map(inner, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"), check_vma=False)(x)
    return fn, (_x(),)


def axis_order_spec():
    """Hierarchical collective listing mesh axes out of mesh order —
    breaks collectives/ops.py's (cross..., intra) convention."""
    mesh = _mesh()

    def fn(x):
        def inner(x):
            return lax.psum(x, ("mp", "dp"))  # <- jax-axis-order
        return shard_map(inner, mesh=mesh, in_specs=P("dp"),
                         out_specs=P(), check_vma=False)(x)
    return fn, (_x(),)


def donated_reuse_spec():
    """A buffer used again after being donated to a jitted call."""
    def fn(x):
        y = jax.jit(lambda v: v + 1, donate_argnums=(0,))(x)
        return y + x  # <- jax-donated-reuse
    return fn, (_x(),)


# ------------------------------------------- rank-parameterized factories
#
# ``analyze_rank_divergence`` consumes factory(rank, size) -> (fn, args):
# the step is re-traced once per simulated rank with the CONCRETE rank
# bound, so host-level ``if rank == 0:`` branches (invisible to a single
# abstract trace — Python already picked the branch) shape each rank's
# collective stream differently and the pairwise diff catches it.

def rank_gated_allreduce_factory(rank, size):
    """The canonical mismatch: rank 0 issues a psum the other ranks never
    reach (reference: horovod/common/controller.cc answers this with a
    mismatch Response at runtime; under GSPMD the job just hangs)."""
    mesh = _mesh()

    def fn(x):
        def inner(x):
            if rank == 0:
                return lax.psum(x, "dp")  # <- jax-rank-divergence
            return x * 1.0
        return shard_map(inner, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"), check_vma=False)(x)
    return fn, (_x(),)


def uniform_allreduce_factory(rank, size):
    """Control: every rank traces the identical stream — rank only picks
    host-side work, the collective is unconditional.  Must produce ZERO
    divergence findings."""
    mesh = _mesh()

    def fn(x):
        def inner(x):
            out = lax.psum(x, "dp")
            return out

        return shard_map(inner, mesh=mesh, in_specs=P("dp"),
                         out_specs=P(), check_vma=False)(x)
    return fn, (_x(),)
