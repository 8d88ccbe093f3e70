"""GPipe-style pipeline parallelism over the ``pod`` axis (optional).

The production mesh's leading axis can act as a pipeline instead of a data
axis: stages live on successive pods and microbatches flow through a
``shard_map`` + ``ppermute`` schedule. The classic GPipe utilisation
(M microbatches over P stages ⇒ (M)/(M+P-1) bubble efficiency) applies.

Kept deliberately small: a composable ``gpipe`` transform for a stacked
per-stage step function, exercised by tests on fake devices and available
to the launcher via ``--pp``. The DP/TP/EP paths inside each stage remain
auto-sharded (partial-manual shard_map over the pipeline axis only).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["gpipe"]


def gpipe(stage_fn: Callable, n_stages: int, n_microbatches: int, *,
          axis: str = "pod", mesh=None):
    """Build a pipelined forward: ``y = pipe(stage_params, x)``.

    ``stage_fn(params_s, x) -> x`` is one stage's computation;
    ``stage_params`` is a pytree whose leaves are stacked on a leading
    stage dimension (sharded over ``axis``); ``x`` is the global batch,
    split into ``n_microbatches`` along dim 0.

    Schedule: at tick t, stage s processes microbatch (t - s); activations
    hop stage s -> s+1 via ``ppermute`` between ticks. Total ticks =
    M + P - 1 (the GPipe bubble).
    """
    assert n_microbatches >= 1

    def pipe(stage_params, x):
        B = x.shape[0]
        assert B % n_microbatches == 0
        mb = B // n_microbatches

        def per_stage(params_stacked, x_all):
            # params_stacked leaves: (1, ...) slice for this stage
            params = jax.tree_util.tree_map(lambda a: a[0], params_stacked)
            stage = jax.lax.axis_index(axis)
            xs = x_all.reshape(n_microbatches, mb, *x_all.shape[1:])
            n_ticks = n_microbatches + n_stages - 1
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

            def tick(carry, t):
                buf, outs = carry
                # stage 0 injects microbatch t (others got theirs via the
                # previous tick's ppermute)
                inject = jnp.where(t < n_microbatches,
                                   jnp.clip(t, 0, n_microbatches - 1), 0)
                x_in = jnp.where(stage == 0, xs[inject], buf)
                y = stage_fn(params, x_in)
                # the microbatch index this stage just produced
                mb_idx = t - stage
                is_last = stage == n_stages - 1
                live = (mb_idx >= 0) & (mb_idx < n_microbatches) & is_last
                outs = jax.lax.cond(
                    live,
                    lambda o: o.at[jnp.clip(mb_idx, 0,
                                            n_microbatches - 1)].set(y),
                    lambda o: o, outs)
                buf2 = jax.lax.ppermute(y, axis, perm)
                return (buf2, outs), None

            buf0 = jnp.zeros((mb, *x_all.shape[1:]), x_all.dtype)
            outs0 = jnp.zeros((n_microbatches, mb, *x_all.shape[1:]),
                              x_all.dtype)
            (_, outs), _ = jax.lax.scan(
                tick, (buf0, outs0), jnp.arange(n_ticks))
            # only the last stage holds real outputs; broadcast them so the
            # result is replicated over the pipeline axis
            outs = jax.lax.psum(
                jnp.where(stage == n_stages - 1, outs, 0.0), axis)
            return outs.reshape(B, *x_all.shape[1:])

        return jax.shard_map(
            per_stage, mesh=mesh,
            in_specs=(P(axis), P()), out_specs=P(),
            axis_names={axis}, check_vma=False,
        )(stage_params, x)

    return pipe
