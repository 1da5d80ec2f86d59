"""Pipeline parallelism: GPipe microbatch scheduling over a mesh axis.

Completes the framework's parallelism alphabet (data = mesh ``data``
axis, tensor = ``model`` axis via models.common TP, sequence = ring
attention, tenant = stacked slots): deep models whose LAYERS outgrow one
chip partition blocks into stages, one stage per device along a
``stage`` axis, and microbatches stream through with activations handed
to the next stage by ``lax.ppermute`` (ICI neighbor exchange).

Schedule: classic GPipe — m microbatches, n stages, m+n-1 ticks; every
device computes every tick (branchless; inactive ticks process garbage
whose results are masked), so the bubble fraction is (n-1)/(m+n-1).
The tick loop unrolls in Python (axis size and microbatch count are
static) — XLA overlaps each tick's compute with the next ppermute.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def pipeline_apply_local(
    stage_params,          # this device's stage params (leading dim sliced)
    x: jnp.ndarray,        # [m, B, ...] microbatched input, replicated
    stage_fn: Callable,    # (stage_params, activation [B, ...]) -> [B, ...]
    axis_name: str,
):
    """Per-device GPipe body (run under shard_map over ``axis_name``)."""
    n = lax.psum(1, axis_name)
    s = lax.axis_index(axis_name)
    m = x.shape[0]
    perm = [(j, (j + 1) % n) for j in range(n)]

    current = jnp.zeros_like(x[0])
    out = jnp.zeros_like(x)
    for t in range(m + n - 1):
        mb = t - s  # which microbatch this device works on at tick t
        # stage 0 ingests microbatch t; later stages use the handed-over
        # activation. Branchless: inactive devices compute on whatever is
        # in the buffer and the result is masked below.
        feed = x[min(t, m - 1)]
        current = jnp.where(s == 0, feed, current)
        y = stage_fn(stage_params, current)
        active = (mb >= 0) & (mb < m)
        # last stage banks its finished microbatch
        done_idx = t - (n - 1)
        if 0 <= done_idx < m:
            bank = (s == n - 1) & active
            out = out.at[done_idx].set(jnp.where(bank, y, out[done_idx]))
        if t < m + n - 2:
            current = lax.ppermute(y, axis_name, perm)
    # only the last stage banked non-zero microbatches; a psum broadcasts
    # them to every device (replicated output, sign-safe unlike pmax)
    return lax.psum(out, axis_name)


def pipeline_apply(
    stage_params_stacked,  # pytree, leading dim = n stages
    x: jnp.ndarray,        # [B, ...] full batch, replicated
    stage_fn: Callable,
    mesh,
    axis_name: str = "stage",
    microbatches: int = 4,
):
    """Run ``x`` through n pipelined stages. ``stage_params_stacked``'s
    leading dim shards one stage per device; activations stream between
    stages; output is the full batch, replicated."""
    n = mesh.shape[axis_name]
    n_stages = jax.tree_util.tree_leaves(stage_params_stacked)[0].shape[0]
    if n_stages != n:
        # a mismatch would SILENTLY drop stages (shard_map blocks the
        # leading dim and the body keeps index 0 of each block)
        raise ValueError(
            f"{n_stages} stacked stages but {n} devices on '{axis_name}'"
        )
    b = x.shape[0]
    if b % microbatches:
        raise ValueError(f"batch {b} must divide into {microbatches} microbatches")
    xm = x.reshape(microbatches, b // microbatches, *x.shape[1:])

    def body(params_local, xm_in):
        params = jax.tree_util.tree_map(lambda a: a[0], params_local)
        return pipeline_apply_local(params, xm_in, stage_fn, axis_name)

    # jitted: an eager shard_map call is interpreted op by op (minutes on
    # the 8-virtual-device CPU rig under jax 0.9); one compile is seconds
    fn = jax.jit(shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
    ))
    out = fn(stage_params_stacked, xm)
    return out.reshape(b, *out.shape[2:])
