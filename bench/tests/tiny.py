"""Cells of the benchmark at a size the CPU runs in seconds: the real
cell's family, traffic shape and limits, with small widths."""
import argparse
import io
import json
import time
from contextlib import redirect_stdout

from bench import spec

MODELS = {
    "mamba2": dict(name="mamba2-tiny", arch_type="ssm", num_layers=2,
                   d_model=128, vocab_size=512, d_ff=0, rope_mode="none",
                   ssm_state=16, ssm_expand=2, ssm_head_dim=32, ssm_chunk=16,
                   tie_embeddings=True, norm_eps=1e-5, dtype="bfloat16",
                   remat=True, scan_layers=True),
    "dense": dict(name="dense-tiny", arch_type="dense", num_layers=2,
                  d_model=128, vocab_size=512, num_heads=4, num_kv_heads=2,
                  head_dim=32, d_ff=256, activation="swiglu", qkv_bias=True,
                  rope_theta=10000.0, rope_mode="standard",
                  norm_eps=1.5625e-07, tie_embeddings=False,
                  dtype="bfloat16", remat=True, scan_layers=True),
}


def cell(name: str, chips: int = None) -> spec.Cell:
    """Cell ``name`` of the benchmark, its model cut to ``MODELS``' size
    and its sequences to 128 tokens."""
    real = spec.cell(name)
    config = dict(real.config, model=MODELS[real.config["reference"]])
    workload = dict(real.workload, seq_len=128)
    return spec.Cell(name=name, chips=chips or real.chips, config=config,
                     workload=workload, limits=real.limits,
                     end_to_end=real.end_to_end, per_layer=real.per_layer)


def run(c: spec.Cell, seed: int = 2 ** 31 + 11) -> dict:
    """A whole run of ``c`` on the CPU, the look for a chip skipped; the
    result line as a dict."""
    import jax
    from bench import harness
    devices = jax.devices()
    peaks = {devices[0].device_kind: {"bf16_flops_per_s": 1e12}}
    args = argparse.Namespace(workload=c.name, seed=seed, seconds=0.5,
                              trace=0)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert harness.run(c, args, devices, peaks, time.perf_counter(),
                           0.0) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])
