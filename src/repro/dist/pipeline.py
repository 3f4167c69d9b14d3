"""GPipe pipeline parallelism over a "stage" mesh axis.

Each device holds one stage's params (leading axis sharded over ``stage``);
microbatches stream through the pipeline with activations handed to the
next stage by ``ppermute`` (point-to-point, lowering to collective-permute
— no all-gather of activations).  The schedule runs
``n_micro + n_stages - 1`` ticks: stage 0 injects microbatch ``t`` at tick
``t``, the last stage emits microbatch ``t - (n_stages - 1)``, and a final
``psum`` replicates the collected outputs (all other stages contribute
zeros).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe_apply(stage_fn, params, xs, mesh, axis: str = "stage"):
    """Apply ``n_stages`` chained ``stage_fn`` s to each microbatch.

    stage_fn : (stage_params, x) -> y with y.shape == x.shape
    params   : pytree with a leading (n_stages, ...) axis on every leaf
    xs       : (n_micro, B, ...) microbatch stream
    Returns (n_micro, B, ...) outputs, replicated across the mesh.
    """
    n_stages = mesh.shape[axis]
    n_micro = xs.shape[0]
    ticks = n_micro + n_stages - 1

    def per_device(p_local, xs_local):
        p = jax.tree.map(lambda a: a[0], p_local)     # this stage's params
        stage = jax.lax.axis_index(axis)
        state = jnp.zeros_like(xs_local[0])
        outs = jnp.zeros_like(xs_local)

        def tick(t, carry):
            state, outs = carry
            inject = xs_local[jnp.minimum(t, n_micro - 1)]
            state = jnp.where(stage == 0, inject, state)
            y = stage_fn(p, state)
            m = t - (n_stages - 1)
            outs = jax.lax.cond(
                jnp.logical_and(stage == n_stages - 1, m >= 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(m, 0), 0),
                lambda o: o, outs)
            y = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return y, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (state, outs))
        return jax.lax.psum(outs, axis)    # replicate (others hold zeros)

    pspec = jax.tree.map(
        lambda a: P(*((axis,) + (None,) * (a.ndim - 1))), params)
    xspec = P(*((None,) * xs.ndim))
    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(pspec, xspec), out_specs=xspec,
        check_vma=False)(params, xs)
