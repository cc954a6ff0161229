"""Distribution layer tests on a small forced-device mesh.

conftest does NOT set XLA_FLAGS (smoke tests must see 1 device), so these
tests spawn a subprocess with 8 forced host devices where needed; pure
logic (specs, plans, compression math) runs in-process.
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.collectives import dequantize_int8, quantize_int8
from repro.dist.sharding import _PARAM_RULES
from repro.train.fault_tolerance import (
    HeartbeatTracker,
    RetryPolicy,
    StragglerMonitor,
    elastic_mesh_plan,
    run_with_restarts,
)


class TestQuantization:
    def test_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal(1000).astype(np.float32))
        q, scale = quantize_int8(g)
        dq = dequantize_int8(q, scale)
        max_err = float(jnp.max(jnp.abs(g - dq)))
        assert max_err <= float(scale) / 2 + 1e-6

    def test_zero_gradient(self):
        g = jnp.zeros(16)
        q, scale = quantize_int8(g)
        assert not np.asarray(q).any()


class TestElasticPlan:
    def test_keeps_tp(self):
        plan = elastic_mesh_plan(512 - 16, model_parallel=16)
        assert plan["model"] == 16
        assert plan["data"] == 31
        assert plan["used_devices"] == 496

    def test_rejects_sub_tp(self):
        with pytest.raises(ValueError):
            elastic_mesh_plan(8, model_parallel=16)


class TestStragglerMonitor:
    def test_flags_outlier(self):
        mon = StragglerMonitor(warmup=4, k_sigma=3.0)
        for i in range(20):
            assert not mon.record(i, 1.0 + 0.01 * (i % 3))
        assert mon.record(20, 5.0)
        assert mon.flagged and mon.flagged[0][0] == 20

    def test_mean_resists_stragglers(self):
        mon = StragglerMonitor(warmup=4)
        for i in range(20):
            mon.record(i, 1.0)
        mon.record(20, 50.0)
        assert mon.mean < 1.5


class TestRetry:
    def test_restarts_then_succeeds(self):
        calls = {"n": 0, "restores": 0}

        def step():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("node died")

        restarts = run_with_restarts(
            step, lambda: calls.__setitem__("restores", calls["restores"] + 1),
            RetryPolicy(max_restarts=5, backoff_s=0), sleep=lambda s: None,
        )
        assert restarts == 2 and calls["restores"] == 2

    def test_gives_up(self):
        def step():
            raise RuntimeError("dead")

        with pytest.raises(RuntimeError):
            run_with_restarts(step, lambda: None,
                              RetryPolicy(max_restarts=2, backoff_s=0),
                              sleep=lambda s: None)


class TestHeartbeats:
    def test_dead_host_detection(self):
        hb = HeartbeatTracker(timeout_s=10)
        hb.beat(0, now=0.0)
        hb.beat(1, now=0.0)
        hb.beat(0, now=8.0)
        assert hb.dead_hosts(now=12.0) == [1]


_SUBPROCESS_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P, NamedSharding

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2), ("data", "model"))

    # --- shuffle_by_key: groups end up whole on one shard -----------------
    from repro.dist.shuffle import shuffle_by_key, shuffle_by_key_host
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 13, (4, 32)).astype(np.int32)
    payload = np.stack([keys, rng.integers(0, 99, (4, 32)).astype(np.int32)], -1)
    payload[..., 0] = keys
    valid = rng.random((4, 32)) < 0.9
    k2, p2, v2, src, ovf = shuffle_by_key(
        jnp.asarray(keys), jnp.asarray(payload), jnp.asarray(valid), mesh
    )
    k2, v2, src = np.asarray(k2), np.asarray(v2), np.asarray(src)
    assert not bool(ovf)
    # every key lives on exactly one shard
    for key in np.unique(keys[valid]):
        shards = [s for s in range(4) if (k2[s][v2[s]] == key).any()]
        assert len(shards) == 1, (key, shards)
    # row conservation
    assert v2.sum() == valid.sum()
    # src is the inverse permutation: routed keys match their source rows
    fk = keys.reshape(-1)
    assert (fk[src[v2]] == k2[v2]).all()
    assert len(set(src[v2].tolist())) == int(v2.sum())  # no slot shares a source
    # matches the host reference semantics shard-for-shard
    hk, hp, hv, hsrc, hovf = shuffle_by_key_host(keys, payload, valid, 4)
    for s in range(4):
        assert sorted(k2[s][v2[s]].tolist()) == sorted(hk[s][hv[s]].tolist())

    # --- compressed gradient all-reduce ------------------------------------
    from repro.dist.collectives import grad_allreduce_compressed
    g = {"w": jnp.asarray(rng.standard_normal((4, 8)).astype(np.float32))}
    e = {"w": jnp.zeros((4, 8), jnp.float32)}
    red, new_e = grad_allreduce_compressed(g, e, mesh)
    # replicated input -> mean == input (all shards equal)
    np.testing.assert_allclose(np.asarray(red["w"]), np.asarray(g["w"]), atol=0.05)

    # --- pipeline_apply (GPipe) --------------------------------------------
    from repro.dist.pipeline import pipeline_apply
    smesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4,), ("stage",))
    sp = jnp.asarray(np.arange(4, dtype=np.float32).reshape(4, 1) + 1.0)
    xm = jnp.asarray(rng.standard_normal((6, 3)).astype(np.float32))
    out = pipeline_apply(lambda p, x: x * p[0], sp, xm, smesh, stages=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(xm) * 24.0, rtol=1e-5)

    print("SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_mesh_collectives_subprocess():
    """shuffle / compressed all-reduce / pipeline on an 8-device mesh."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_TEST],
        capture_output=True, text=True, timeout=1200,
        # the child must never reach for an accelerator the parent may hold
        env={"PYTHONPATH": os.path.join(repo_root, "src"),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "JAX_PLATFORMS": "cpu"},
        cwd=repo_root,
    )
    assert "SUBPROCESS_OK" in res.stdout, res.stdout + "\n" + res.stderr


class TestParamRules:
    def test_all_rules_resolve(self):
        for name, rule in _PARAM_RULES.items():
            for entry in rule:
                assert entry in (None, "fsdp", "tp"), (name, entry)
