"""The Pallas kernels and the fixed-point grad-sync path compile for a TPU
v5e: compiles against a described ``v5e:2x2`` topology, with no chip.

Nothing here runs: a compile that passes says the TPU compiler (Mosaic for
the kernels) accepts the program at these shapes, which interpret-mode
tests cannot show. The kernels pick their mode from the platform, and the
platform here is the CPU, so each test steers that choice to the TPU with
``monkeypatch``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import mode
from repro.kernels.fixedpoint import dequantize, quantize
from repro.kernels.packet_accum import packet_accumulate


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


@pytest.fixture
def one_chip(topo, monkeypatch):
    monkeypatch.setattr(mode, "interpret", lambda: False)
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_quantize_dequantize_compile_at_largest_gradient_leaf(one_chip):
    """mamba2-130m's embedding gradient, 50280 x 768, is the largest leaf
    the fixed-point grad sync quantizes."""
    shape = (50280, 768)
    scale = _sds((), jnp.float32, one_chip)
    q = jax.jit(quantize).lower(_sds(shape, jnp.float32, one_chip),
                                scale).compile()
    d = jax.jit(dequantize).lower(_sds(shape, jnp.int32, one_chip),
                                  scale).compile()
    assert _kernel_calls(q) == 1 and _kernel_calls(d) == 1


@pytest.mark.parametrize("n,d,slots", [(48, 256, 12), (300, 1000, 13)])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_packet_accumulate_compiles(one_chip, dtype, n, d, slots):
    """Replay rounds: tens of packets of one 1 KiB block (256 words) into a
    round's slots, and a multi-tile case with unaligned sizes."""
    c = jax.jit(lambda ids, pay: packet_accumulate(ids, pay, slots)).lower(
        _sds((n,), jnp.int32, one_chip),
        _sds((n, d), dtype, one_chip)).compile()
    assert _kernel_calls(c) == 1


def _fp_grads(topo, data, model):
    """The trainer's ``canary_fp`` gradient program on a described
    (data, model) mesh, params placed as the Trainer places them for an
    explicit mode; returns it compiled, with the param shapes and specs."""
    from repro.models import get_config, init_params
    from repro.parallel.sharding import batch_spec, param_specs
    from repro.train import TrainConfig, make_grads_fn

    mesh = Mesh(np.asarray(topo.devices[:data * model]).reshape(data, model),
                ("data", "model"))
    cfg = get_config("mamba2-130m", "smoke").with_(num_layers=1)
    shapes = jax.eval_shape(partial(init_params, cfg), jax.random.PRNGKey(0))
    specs = param_specs(shapes, mesh, fsdp="data", model="model",
                        use_fsdp=False)
    params = jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, NamedSharding(mesh, sp)),
        shapes, specs)
    tokens = _sds((8, 64), jnp.int32,
                  NamedSharding(mesh, batch_spec(mesh, 8, "data")))
    tc = TrainConfig(model=cfg, grad_sync="canary_fp")
    c = jax.jit(make_grads_fn(tc, mesh)).lower(
        params, {"tokens": tokens, "labels": tokens}).compile()
    return c, mesh, shapes, specs


@pytest.mark.parametrize("chips", [1, 4])
def test_fixed_point_grad_sync_compiles_in_trainer_shard_map(
        topo, monkeypatch, chips):
    """The trainer's ``canary_fp`` gradients: the fixed-point kernels sit
    inside a shard_map that is manual over the data axis only, which the
    compiler refuses unless the kernels are kept out of its partitioner."""
    monkeypatch.setattr(mode, "interpret", lambda: False)
    c, _, shapes, _ = _fp_grads(topo, chips, 1)
    # one quantize and one dequantize per gradient leaf
    assert _kernel_calls(c) == 2 * len(jax.tree.leaves(shapes))


def test_fixed_point_kernels_see_only_their_model_shard(topo, monkeypatch):
    """On a (2, 2) mesh the model axis stays automatic in the grad-sync
    shard_map. Each quantize and dequantize call must get only the device's
    own part of its gradient leaf, as the param's spec splits it: a leaf
    gathered over the model axis first would reach the kernel whole, and the
    integer tree would then carry it whole on every device."""
    from repro.kernels.fixedpoint import TILE_COLS, TILE_ROWS

    monkeypatch.setattr(mode, "interpret", lambda: False)
    c, mesh, shapes, specs = _fp_grads(topo, 2, 2)

    def kernel_rows(shape, spec):
        split = 1
        for axes in spec:
            for a in (axes,) if isinstance(axes, str) else axes or ():
                split *= mesh.shape[a]
        rows = -(-int(np.prod(shape)) // split // TILE_COLS)
        return -(-rows // TILE_ROWS) * TILE_ROWS

    leaves = jax.tree.leaves(shapes)
    want = sorted(2 * [kernel_rows(s.shape, sp) for s, sp in zip(
        leaves, jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))])
    whole = sorted(2 * [kernel_rows(s.shape, P()) for s in leaves])
    assert want != whole                          # some leaves are split
    got = sorted(int(m) for m in re.findall(
        r"= [a-z0-9]+\[(\d+),\d+\][^ ]* custom-call\([^\n]*tpu_custom_call",
        c.as_text()))
    assert got == want
