"""Canary TPU-collective correctness on a multi-device (simulated) mesh.

This file re-executes itself in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps its single-device view (per the dry-run isolation rule).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

SCRIPT = r"""
import os
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.collective import (canary_allreduce_tree,
                                   hierarchical_allreduce,
                                   multi_root_tree_allreduce, ring_allreduce,
                                   tree_reduce_broadcast)

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
N = 8

def run(fn, x):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"),
                                 check_vma=False))(x)

key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (8, 64)).astype(jnp.float32)
want = np.broadcast_to(np.asarray(x).sum(0, keepdims=True), (8, 64))

# 1) single binomial tree, every root
for root in range(N):
    got = run(lambda v, r=root: tree_reduce_broadcast(v, "data", N, r), x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
print("tree roots ok")

# 2) multi-root blockwise
for roots in ([0] * 4, list(range(4)), [3, 1, 4, 1, 5, 0, 2, 6]):
    got = run(lambda v, rr=tuple(roots): multi_root_tree_allreduce(
        v, "data", N, rr), x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
print("multi-root ok")

# 3) ring reduce-scatter/all-gather
got = run(lambda v: ring_allreduce(v, "data"), x)
np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
print("ring ok")

# 4) odd sizes / padding
x3 = jax.random.normal(key, (8, 37))
want3 = np.broadcast_to(np.asarray(x3).sum(0, keepdims=True), (8, 37))
got = run(lambda v: multi_root_tree_allreduce(v, "data", N, (0, 3, 5)), x3)
np.testing.assert_allclose(np.asarray(got), want3, rtol=1e-5, atol=1e-5)
print("padding ok")

# 5) pytree API + fixed point determinism
tree = {"a": x, "b": x3}
got = jax.jit(jax.shard_map(
    lambda t: canary_allreduce_tree(t, axis_name="data", axis_size=N,
                                    num_blocks=4),
    mesh=mesh, in_specs=({"a": P("data"), "b": P("data")},),
    out_specs={"a": P("data"), "b": P("data")}, check_vma=False))(tree)
np.testing.assert_allclose(np.asarray(got["a"]), want, rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(np.asarray(got["b"]), want3, rtol=1e-5, atol=1e-5)
print("pytree ok")

# 6) fixed-point canary: equal across different root assignments (bitwise)
outs = []
for roots in (tuple(range(8)), (7, 6, 5, 4, 3, 2, 1, 0)):
    got = jax.jit(jax.shard_map(
        lambda t, rr=roots: canary_allreduce_tree(
            t, axis_name="data", axis_size=N, roots=rr, fixed_point=True),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))(x)
    outs.append(np.asarray(got))
np.testing.assert_array_equal(outs[0], outs[1])
np.testing.assert_allclose(outs[0], want, rtol=1e-3, atol=1e-3)
print("fixed-point deterministic ok")

# 7) hierarchical on a 2x4 mesh
mesh2 = jax.make_mesh((2, 4), ("pod", "data"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
xx = jax.random.normal(key, (8, 32))
want2 = np.broadcast_to(np.asarray(xx).sum(0, keepdims=True), (8, 32))
got = jax.jit(jax.shard_map(
    lambda v: hierarchical_allreduce(v, "data", "pod"), mesh=mesh2,
    in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
    check_vma=False))(xx)
np.testing.assert_allclose(np.asarray(got), want2, rtol=1e-5, atol=1e-5)
print("hierarchical ok")

# 8) fixed-point canary with the model axis left automatic: each leaf is
#    quantized and reduced as its spec splits it over "model"
mesh3 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
x3 = jax.device_put(jax.random.normal(key, (4, 16, 32)),
                    jax.sharding.NamedSharding(mesh3, P("data", "model")))
want8 = np.broadcast_to(np.asarray(x3).sum(0, keepdims=True), (4, 16, 32))
outs = []
for roots in ((0, 1, 2, 3), (3, 2, 1, 0)):
    got = jax.jit(jax.shard_map(
        lambda t, rr=roots: canary_allreduce_tree(
            {"a": t, "b": 2 * t}, axis_name="data", axis_size=4, roots=rr,
            fixed_point=True, leaf_specs={"a": P(None, "model"), "b": P()}),
        mesh=mesh3, in_specs=P("data"), out_specs=P("data"),
        axis_names={"data"}, check_vma=False))(x3)
    outs.append(jax.tree.map(np.asarray, got))
for k, scale in (("a", 1), ("b", 2)):
    np.testing.assert_array_equal(outs[0][k], outs[1][k])
    np.testing.assert_allclose(outs[0][k], scale * want8, rtol=1e-3,
                               atol=1e-3)
print("fixed-point over an automatic model axis ok")
print("ALL_OK")
"""


def test_collectives_on_8_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.dirname(os.path.abspath(__file__)))))
    assert "ALL_OK" in proc.stdout, proc.stdout + "\n" + proc.stderr


def test_link_load_model_properties():
    from repro.core.collective import tree_link_load
    for n in (4, 8, 16):
        total_per_root = [tree_link_load(r, n).sum() for r in range(n)]
        # total traffic is root-invariant (same tree, rotated)
        assert max(total_per_root) - min(total_per_root) < 1e-9
        # rotating the root rotates the load vector
        l0 = tree_link_load(0, n)
        l3 = tree_link_load(3, n)
        np.testing.assert_allclose(np.roll(l0, 3), l3)


def test_oracle_round_robin_matches_paper_policy():
    from repro.core.collective import CongestionOracle, round_robin_roots
    rr = round_robin_roots(10, 4)
    assert rr == [0, 1, 2, 3, 0, 1, 2, 3, 0, 1]
    o = CongestionOracle(axis_size=4, num_blocks=10, policy="round_robin")
    assert o.plan() == rr


def test_oracle_balanced_avoids_hotspot():
    import numpy as np
    from repro.core.collective import CongestionOracle, tree_link_load
    n, blocks = 8, 32
    ext = np.zeros(n)
    ext[0:2] = 1000.0  # another tenant hammering links 0-1
    hot = CongestionOracle(axis_size=n, num_blocks=blocks, policy="balanced",
                           external_load=ext)
    plan = hot.plan()
    load = ext.copy()
    for r in plan:
        load += tree_link_load(r, n)
    rr_load = ext.copy()
    from repro.core.collective import round_robin_roots
    for r in round_robin_roots(blocks, n):
        rr_load += tree_link_load(r, n)
    assert load.max() <= rr_load.max()


def test_oracle_feedback_updates_weights():
    from repro.core.collective import CongestionOracle
    o = CongestionOracle(axis_size=4, num_blocks=8)
    for t in (0.1, 0.1, 0.1, 0.5):
        o.feedback(t)
    assert o.plan()  # still plans after feedback
