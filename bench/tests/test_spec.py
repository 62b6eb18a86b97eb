"""``BENCHMARK.json`` and the files it names: every configuration, traffic
mix, limit set and metric is found by its name, and the file keeps the
benchmark's own rules."""
import re

import jax
import pytest

from bench import check, spec
from bench.reference import family

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                   r"proj|head|expand|d_model|d_ff|experts_per_tok|top_k")


def test_every_name_finds_its_file():
    for c in BENCH["configs"]:
        assert spec.find("configs", c["name"]).endswith(c["file"][6:])
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"])
        assert set(check.NUMBERS) <= set(cell.limits)
        assert cell.chips in (1, 4)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(spec.reader(m["name"]).read)


def test_find_is_exact():
    with pytest.raises(FileNotFoundError):
        spec.find("configs", "mamba2")
    with pytest.raises(FileNotFoundError):
        spec.find("metrics", "no_such_metric")


def test_names_and_limits_of_the_file():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(x["why"]) <= 200, x["name"]
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_each_cell_reports_what_it_must():
    e2e = BENCH["end_to_end"]
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"])
        got = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in got


def test_reduced_keys_are_the_changed_ones():
    for c in BENCH["configs"]:
        conf = spec.load_json(spec.find("configs", c["name"]))
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        for key in c["reduced"]:
            assert key in conf["model"] and not WIDTH.search(key), key


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_reference_weights_match_the_program_layout(name):
    """The reference's weights have the program's tree, shapes and types,
    and as many parameters as the configuration file states."""
    from repro.models import ModelConfig, init_params
    conf = spec.load_json(spec.find("configs", name))
    fam = family(conf["reference"])
    ref = jax.eval_shape(lambda k: jax.tree.map(
        lambda x, t: x.astype(t), fam.init(conf["model"], k),
        fam.served_dtypes(conf["model"])), check.seed_key(0))
    prog = jax.eval_shape(lambda k: init_params(ModelConfig(**conf["model"]),
                                                k), jax.random.PRNGKey(0))
    assert jax.tree.structure(ref) == jax.tree.structure(prog)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(prog)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert sum(x.size for x in jax.tree.leaves(ref)) == conf["parameters"]
