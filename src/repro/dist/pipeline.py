"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

``pipeline_apply(fn, stage_params, x, mesh, stages)`` places stage ``s``'s
parameter slice on mesh coordinate ``s``, splits the batch into
microbatches, and runs the classic fill/steady/drain schedule: at step
``t`` stage ``s`` processes microbatch ``t - s``, shifting activations to
the next stage with ``ppermute`` between steps.  Stage functions must be
shape-preserving (activation in == activation out), which is the
transformer-block case this targets.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    fn: Callable,
    stage_params,
    x: jnp.ndarray,
    mesh,
    stages: Optional[int] = None,
    axis: str = "stage",
    n_micro: Optional[int] = None,
) -> jnp.ndarray:
    """Apply ``stages`` copies of ``fn`` sequentially, pipelined.

    ``stage_params`` is a pytree whose leaves carry a leading ``stages``
    dim (stage s uses slice s).  ``x`` is the global batch; ``n_micro``
    defaults to one microbatch per batch row.
    """
    stages = stages or mesh.shape[axis]
    n_micro = n_micro or x.shape[0]
    if x.shape[0] % n_micro:
        raise ValueError(f"batch {x.shape[0]} not divisible by {n_micro} microbatches")
    mb = x.shape[0] // n_micro
    perm = [(i, (i + 1) % stages) for i in range(stages)]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    def run(p_local, xg):
        p = jax.tree.map(lambda a: a[0], p_local)  # drop the local stage dim
        s = jax.lax.axis_index(axis)
        is_first = s == 0
        is_last = s == stages - 1
        micro = xg.reshape((n_micro, mb) + xg.shape[1:])
        buf = jnp.zeros_like(micro[0])
        out = jnp.zeros_like(micro)
        for t in range(n_micro + stages - 1):
            # stage 0 injects microbatch t; later stages consume the
            # activation shifted in from stage s-1 last step
            state_in = jnp.where(is_first, micro[min(t, n_micro - 1)], buf)
            y = fn(p, state_in)
            m = t - (stages - 1)  # microbatch finishing at the last stage
            if 0 <= m < n_micro:
                out = out.at[m].set(jnp.where(is_last, y, 0.0))
            buf = jax.lax.ppermute(y, axis, perm)
        # only the last stage wrote non-zeros; psum replicates its result
        return jax.lax.psum(out.reshape(xg.shape), axis)

    return run(stage_params, x)
