"""Each one-chip cell's training step compiles for a TPU v5e at its real
size and fits the chip: a compile against a described ``v5e:2x2``
topology, with no chip. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench import cell as training
from bench import spec

V5E_HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compiled_step(cell, devices):
    """The Trainer's step compiled for ``devices``, its arguments placed as
    the Trainer places them."""
    from repro.optim import AdamWState
    from repro.parallel.context import parallel_context
    from repro.parallel.sharding import batch_spec, param_shardings
    from repro.train.train_step import init_train_state, make_train_step
    from functools import partial

    cfg, mesh, ctx = training.build(cell, 0, devices)
    tc = cfg.train
    init = partial(init_train_state, tc)
    params, opt = jax.eval_shape(init, jax.random.PRNGKey(0))
    p_shard = param_shardings(params, mesh, fsdp="data", model="model",
                              use_fsdp=tc.grad_sync == "auto")
    rep = NamedSharding(mesh, P())
    sds = lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
    params = jax.tree.map(sds, params, p_shard)
    opt = AdamWState(step=sds(opt.step, rep),
                     m=jax.tree.map(sds, opt.m, p_shard),
                     v=jax.tree.map(sds, opt.v, p_shard))
    B, S = cfg.data.global_batch, cfg.data.seq_len
    bs = NamedSharding(mesh, batch_spec(mesh, B, "data"))
    batch = {k: jax.ShapeDtypeStruct((B, S), jax.numpy.int32, sharding=bs)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(tc, mesh=mesh, dp_axes=("data",)),
                   donate_argnums=(0, 1))
    with parallel_context(ctx):
        return step.lower(params, opt, batch).compile()


@pytest.mark.parametrize("name", spec.cell_names(1))
def test_cell_step_fits_one_v5e(topo, name):
    cell = spec.cell(name)
    compiled = _compiled_step(cell, topo.devices)
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < need < V5E_HBM, (name, need)
