"""GPipe-style pipeline parallelism over the 'pod' mesh axis (opt-in).

The default multi-pod recipe in this framework is DP across pods (gradients
cross the DCN once per step). When activations are smaller than gradients —
very deep, narrow models — pipelining the *stages* across pods wins
instead. This module provides that alternative: stage the layer stack over
the 'pod' axis, microbatch the global batch, and run the 1F1B-ish schedule
with ``jax.lax`` collectives (ppermute between stages).

Implementation notes:
  * stages hold contiguous slices of the unit stack (equal unit counts);
  * boundary activations move stage->stage via ``collective_permute``;
  * the schedule is the classic "pipelined scan": with M microbatches and
    P stages, a scan of length M+P-1 where stage p is active for ticks
    [p, p+M); bubble fraction = (P-1)/(M+P-1).

This is exercised by tests on a host mesh (tests/test_pipeline.py) and is
selectable in the training driver with ``--pipeline``; it is NOT part of
the default dry-run matrix (DESIGN.md explains the DP-across-pods choice).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    fn_stage: Callable,      # (stage_params, x, stage_idx) -> x
    stage_params,            # pytree stacked on leading axis = n_stages
    x,                       # (M, mb, L, D) microbatched input
    mesh: Mesh,
    axis: str = "pod",
):
    """Run the pipelined forward under shard_map over ``axis``.

    Returns the final-stage outputs, microbatched (M, mb, L, D).
    """
    n_stages = mesh.shape[axis]
    M = x.shape[0]

    def local(stage_p, x_l):
        # x_l: (M, mb, L, D) — only stage 0 reads it; others get zeros flow
        stage = jax.lax.axis_index(axis)
        mb_shape = x_l.shape[1:]
        ticks = M + n_stages - 1

        def tick(carry, t):
            outputs = carry
            # which microbatch this stage works on at tick t
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < M)
            x_in = jax.lax.dynamic_index_in_dim(
                x_l, jnp.clip(mb_idx, 0, M - 1), 0, keepdims=False
            )
            # non-first stages consume the previous stage's activation
            recv = jax.lax.ppermute(
                outputs["boundary"], axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            x_eff = jnp.where(stage == 0, x_in, recv)
            y = fn_stage(stage_p, x_eff, stage)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage writes result for microbatch mb_idx
            out_acc = jax.lax.cond(
                active & (stage == n_stages - 1),
                lambda acc: acc.at[jnp.clip(mb_idx, 0, M - 1)].set(y),
                lambda acc: acc,
                outputs["acc"],
            )
            return {"boundary": y, "acc": out_acc}, None

        init = {
            "boundary": jnp.zeros(mb_shape, x_l.dtype),
            "acc": jnp.zeros((M,) + mb_shape, x_l.dtype),
        }
        out, _ = jax.lax.scan(tick, init, jnp.arange(ticks))
        # only the last stage's acc is meaningful; broadcast it
        acc = jax.lax.ppermute(
            out["acc"], axis,
            [((n_stages - 1 + i) % n_stages, i) for i in range(n_stages)],
        ) if n_stages > 1 else out["acc"]
        return acc

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
