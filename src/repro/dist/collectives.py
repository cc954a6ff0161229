"""Compressed cross-replica collectives (DESIGN.md §6).

Gradient all-reduce with int8 quantization and error feedback: each data-
parallel rank quantizes (gradient + carried residual) to int8 with a single
per-tensor scale, all-reduces the dequantized value, and carries the
quantization error into the next step (1-bit-Adam / DGC style error
feedback, which keeps SGD convergence despite the lossy wire format).

The contract callers rely on (wired into train/steps.py behind the
``grad_compress`` flag):

* **quantization** is symmetric per-tensor int8: ``q = round(x / scale)``
  clipped to [-127, 127] with ``scale = amax / 127`` (``scale = 1`` for an
  all-zero tensor, so zeros round-trip exactly);
* **error feedback**: the value quantized is ``gradient + residual``; the
  new residual is ``(gradient + residual) - dequantize(q)``, a per-leaf
  f32 pytree the CALLER carries between steps (``opt_state["gerr"]`` in
  the training step — ``init_opt_state(grad_compress=True)`` allocates
  it).  Residuals are rank-local state and are never reduced;
* **reduction** is a mean over the data-parallel mesh axes of the
  dequantized value, so the result has gradient dtype and magnitude —
  drop-in for the uncompressed mean-reduce;
* **shapes/dtypes**: any pytree of real-valued leaves; residual leaves are
  f32 with the leaf's shape regardless of gradient dtype.

This is the reference form: inputs enter replicated, which pins the
numerics but means no int8 crosses the wire standalone — realizing the
bytes-on-wire saving needs ``per_rank`` fused inside a manual-DP
``shard_map`` of the step itself (see ``grad_allreduce_compressed``).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import dp_axes


def quantize_int8(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-tensor int8: returns (q int8, scale f32 scalar)."""
    amax = jnp.max(jnp.abs(g.astype(jnp.float32)))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def grad_allreduce_compressed(grads, errors, mesh):
    """Mean-reduce a gradient pytree over the data-parallel axes with int8
    compression + error feedback.  ``errors`` is the residual pytree from
    the previous step (zeros at step 0).  Returns (reduced, new_errors).

    This is the reference form: inputs enter replicated (in_specs P()),
    which pins the numerics — quantize(grad + residual), pmean the
    dequantized value, carry the quantization error — but means no int8
    actually crosses the wire standalone.  Realizing the bytes-on-wire
    saving requires fusing ``per_rank`` inside the training step's own
    shard_map, where each DP rank still holds a distinct local gradient
    (the ROADMAP wiring step); the compression math and tests carry over
    unchanged.
    """
    axes = dp_axes(mesh)

    def per_rank(g, e):
        compensated = g.astype(jnp.float32) + e
        q, scale = quantize_int8(compensated)
        dq = dequantize_int8(q, scale)
        reduced = jax.lax.pmean(dq, axes) if axes else dq
        return reduced.astype(g.dtype), compensated - dq

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def run(gs, es):
        pairs = jax.tree.map(per_rank, gs, es)
        red = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
        err = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
        return red, err

    return run(grads, errors)
