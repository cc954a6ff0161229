"""The cleaning path's DC kernel compiles for a TPU v5e — checked without a
chip, against a described ``v5e:2x2`` topology (DESIGN.md §7).

Interpret mode (tests/test_kernels.py) checks the kernel's results but not
Mosaic's tiling rules; these compiles check what the chip's compiler would
refuse, at the real block and table size, for every encoding dtype the
planner can pick, for a sparse worklist, and for the per-shard scan under
``shard_map`` on four chips.

Only one process at a time may load the TPU compiler's library, and it keeps
it until it exits, so the topology is described inside a module fixture —
never at import — so that every test worker collects the same tests and
only a worker that runs one of them loads the library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.constraints import flip_op
from repro.core.detect import _T1_REDUCE
from repro.dist import detect as dist_detect
from repro.kernels import ops as kops
from repro.kernels.dc_pairs import dc_pair_scan_pallas

N = 1 << 18
BLOCK = 256
NB = N // BLOCK
OPS = ("<", ">")
FLIPPED = tuple(flip_op(op) for op in OPS)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chip-less compile can be written to the persistent cache but never
    # read back from it: keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_scan(one_chip, dtypes, **restr):
    def scan(*args):
        cols, (rs, cs) = list(args[:-2]), args[-2:]
        return dc_pair_scan_pallas(
            cols, cols, OPS, FLIPPED, rs, cs,
            [_T1_REDUCE[op] for op in OPS],
            [_T1_REDUCE[op] for op in FLIPPED],
            block=BLOCK, **restr,
        )

    shapes = [
        jax.ShapeDtypeStruct((N,), jnp.dtype(dt), sharding=one_chip)
        for dt in list(dtypes) + ["bool", "bool"]
    ]
    return jax.jit(scan).lower(*shapes).compile().as_text()


# every dtype plan_dc_encodings can choose: orig int32/f32, int8, bf16, and
# the int8/int16/int32 rank codes
@pytest.mark.parametrize("dtype", ["int32", "float32", "int8", "bfloat16", "int16"])
def test_dense_scan_compiles(one_chip, dtype):
    assert "tpu_custom_call" in _compile_scan(one_chip, [dtype, dtype])


def test_sparse_worklist_compiles_mixed_encodings(one_chip):
    """A ledger-style worklist (scattered row and col block subsets) over
    an int8-encoded atom next to an unencoded f32 one."""
    rng = np.random.default_rng(0)
    text = _compile_scan(
        one_chip, ["int8", "float32"],
        row_block_ids=np.sort(rng.choice(NB, NB // 5, replace=False)),
        col_block_ids=np.sort(rng.choice(NB, 3 * NB // 10, replace=False)),
    )
    assert "tpu_custom_call" in text


def test_per_shard_scan_compiles_on_four_chips(topo, monkeypatch):
    """The vmapped fused scan of sharded detection under ``shard_map``,
    one routed shard per chip of a (data, model) = (4, 1) mesh."""
    # the local scan picks its path from the backend, which is the CPU here
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    ops = ("==", "<", ">")
    flipped = tuple(flip_op(op) for op in ops)
    local = dist_detect._dc_local_scan(
        ops, flipped, tuple(_T1_REDUCE[op] for op in ops),
        tuple(_T1_REDUCE[op] for op in flipped), BLOCK, NB // 2,
    )
    run = dist_detect._per_shard_fn(local, mesh, 4)
    sharding = NamedSharding(mesh, P("data"))

    def routed(dtype):
        return jax.ShapeDtypeStruct((4, N), jnp.dtype(dtype), sharding=sharding)

    cols = (routed("int32"), routed("float32"), routed("float32"))
    text = run.lower((cols, cols, routed("bool"), routed("bool"))).compile().as_text()
    assert "tpu_custom_call" in text
