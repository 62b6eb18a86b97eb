"""The benchmark runs only on a TPU of a kind in its peak table."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

from bench import harness, spec


def _fake(monkeypatch, platform, kind, n):
    devs = [SimpleNamespace(platform=platform, device_kind=kind)] * n
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_unknown_device_kind_is_an_error(monkeypatch):
    _fake(monkeypatch, "tpu", "TPU v99 imaginary", 4)
    with pytest.raises(harness.NoDevice, match="peak table"):
        harness.check_device(1, spec.peaks())


def test_cpu_and_too_few_chips_are_errors(monkeypatch):
    _fake(monkeypatch, "cpu", "cpu", 4)
    with pytest.raises(harness.NoDevice, match="no TPU"):
        harness.check_device(1, spec.peaks())
    _fake(monkeypatch, "tpu", "TPU v5 lite", 1)
    with pytest.raises(harness.NoDevice, match="4 chips"):
        harness.check_device(4, spec.peaks())
    _fake(monkeypatch, "tpu", "TPU v5 lite", 4)
    assert len(harness.check_device(4, spec.peaks())) == 4


def test_every_peak_names_its_source():
    for kind, row in spec.peaks().items():
        assert row["bf16_flops_per_s"] > 0 and row["source"], kind


def test_run_exits_nonzero_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = spec.benchmark()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--workload",
         cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no result" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
