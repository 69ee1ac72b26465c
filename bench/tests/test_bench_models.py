"""The reference's seam: a configuration brings its own model module
(leaves, batch gradient, FLOP count) by a ``reference`` key, and the
shared reference, the readings and the FLOP count take it with no edit."""
import json

import numpy as np
import pytest

from bench import flops, gen, reference
from bench import run as R
from bench_tiny import ROOT, tiny_cell

TOY = "tests/data/toy_reference.py"
CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))
SEED = 2**31 + 77


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_configurations_without_a_reference_key_are_the_dense_model(path):
    cfg = json.loads(path.read_text())
    assert "reference" not in cfg
    assert reference.model_for(cfg) is reference.DENSE
    assert reference.leaf_specs(cfg) == reference.dense_leaf_specs(cfg)


@pytest.fixture(scope="module")
def toy_cell():
    cell = tiny_cell("gpt3-xl.ckpt")
    cell.model["reference"] = TOY
    return cell


def test_a_reference_key_selects_the_configurations_module(toy_cell):
    toy = reference.model_for(toy_cell.model)
    assert toy is not reference.DENSE
    assert toy is reference.model_for(dict(toy_cell.model))    # loaded once
    assert set(reference.leaf_specs(toy_cell.model)) == \
        set(reference.dense_leaf_specs(toy_cell.model)) | {"router"}
    idx = reference.sample_index(toy_cell.model, SEED)
    assert set(idx) == set(reference.leaf_specs(toy_cell.model))
    params = reference.init_params(toy_cell.model, SEED)
    assert params["router"].shape == (toy_cell.model["d_model"], toy.EXPERTS)


def test_the_models_batch_gradient_is_taken_whole(toy_cell):
    """The toy's loss holds a batch-level term, so its mean over the batch
    is not the mean of its rows' losses, as the dense model's is."""
    cfg, tr = toy_cell.model, toy_cell.traffic
    params = reference.init_params(cfg, SEED)
    tokens, labels = gen.batch_at(tr, cfg["vocab_size"], SEED, 0)
    whole, g = reference.batch_grad(params, tokens, labels, cfg)
    rows = [reference.batch_grad(params, tokens, labels, cfg, rows=[r])[0]
            for r in range(tokens.shape[0])]
    assert abs(whole - np.mean(rows)) > 1e-4 * abs(whole)
    assert float(np.abs(np.asarray(g["router"])).max()) > 0
    dense = dict(cfg)
    del dense["reference"]
    d_whole = reference.batch_grad({k: v for k, v in params.items()
                                    if k != "router"}, tokens, labels,
                                   dense)[0]
    assert d_whole < whole       # the balance term is positive


def test_the_three_compared_steps_run_the_configurations_model(toy_cell):
    ref = R.reference_run(toy_cell, SEED)
    assert len(ref["loss"]) == R.COMPARED_STEPS
    assert all(np.isfinite(ref["loss"]))
    names = set(reference.leaf_specs(toy_cell.model))
    assert set(ref["grad"]) == names and set(ref["change"]) == names
    assert ref["change"]["router"][0] > 0
    control = R.reference_run(toy_cell, SEED, mode="fp8")
    assert control["loss"] != ref["loss"]


def test_flops_are_the_configurations_own(toy_cell):
    cfg, b, s = toy_cell.model, 4, 64
    toy = reference.model_for(cfg)
    assert flops.flops_per_token(cfg, s) == toy.flops_per_token(cfg, s)
    assert flops.flops_per_step(cfg, b, s) == \
        b * s * (flops.dense_flops_per_token(cfg, s)
                 + 6 * cfg["d_model"] * toy.EXPERTS)


def test_a_missing_reference_file_raises_naming_the_configuration():
    cfg = dict(tiny_cell("gpt3-xl.ckpt").model,
               reference="references/no_such_model.py")
    with pytest.raises(FileNotFoundError, match="gpt3-xl"):
        reference.model_for(cfg)
    with pytest.raises(FileNotFoundError, match="gpt3-xl"):
        flops.flops_per_step(cfg, 4, 64)
