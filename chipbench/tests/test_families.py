"""A configuration's family: both Mistral configurations build, through
``families/llama.py``, the weights that ``reference.make_weights`` makes for
the seed, bit for bit, and the model holds those arrays (what
``drivers/common.build_model`` did before the move)."""

import hashlib
import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2 ** 31 + 5


def rehearsal(config_name):
    from chipbench.drivers.common import model_sizes, sized
    with open(os.path.join(HERE, "..", "configs", config_name + ".json")) as f:
        config = sized(json.load(f), rehearse=True)
    return config, model_sizes(config)


def digest(weights):
    h = hashlib.sha256()
    for name in sorted(weights):
        h.update(name.encode())
        h.update(np.asarray(weights[name]).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config_name", ["mistral-7b-v0.3-serve",
                                         "mistral-7b-v0.3-train"])
def test_the_family_builds_the_references_weights(config_name):
    import jax.numpy as jnp
    from chipbench import reference
    from chipbench.drivers.common import family
    from chipbench.families import llama
    config, sizes = rehearsal(config_name)
    fam = family(config)
    assert fam is llama                 # no ``family`` key: the default
    model, cfg, weights = fam.build_model(sizes, SEED, config.get("train"))
    plain = reference.make_weights(sizes, SEED, jnp.dtype(cfg.dtype))
    assert digest(weights) == digest(plain)
    held = {n: p._data for n, p in model.named_parameters()}
    assert digest(held) == digest(plain)
    assert fam.logits is reference.logits and fam.loss is reference.loss
    assert fam.make_weights is reference.make_weights


def test_a_configuration_can_name_another_family():
    from chipbench.drivers.common import family
    from chipbench.families import llama
    assert family({"family": "llama"}) is llama
    with pytest.raises(ModuleNotFoundError):
        family({"family": "no_such_family"})
