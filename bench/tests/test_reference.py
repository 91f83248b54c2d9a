"""The plain reference against the program's ``protocol.train``, and the
control against the limits, at a size the CPU runs."""
import json

import jax
import numpy as np
import pytest

from conftest import REPO, TINY

from bench import check, data, program
from bench.references import logistic as ref


def tiny_config(name):
    config = json.loads((REPO / "bench" / "configs" / f"{name}.json")
                        .read_text())
    config.update(TINY)
    return config


def limit(workload):
    return json.loads((REPO / "bench" / "limits" / f"{workload}.json")
                      .read_text())["limits"]["w_rel_err"]


CASES = [("cpml-case1-mnist37", "case1-mnist37.scan-jobs"),
         ("cpml-case1-mnist10-p30", "case1-mnist10-p30.scan-jobs")]


@pytest.mark.parametrize("name,workload", CASES)
def test_reference_follows_protocol_train(name, workload):
    config = tiny_config(name)
    x, y = data.make_dataset(config, 2**35 + 5)
    cfg = program.coded_config(config, 1)
    for j in range(2):
        key = jax.random.fold_in(data.stream(9, data.JOBS), j)
        w, _ = program.protocol.train(cfg, key, x, y, 20)
        w_ref = ref.train(config, x, y, key, 20)
        assert check.rel_err(w, w_ref) <= 1e-6
        assert check.rel_err(w, w_ref) < limit(workload)


@pytest.mark.parametrize("name,workload", CASES)
def test_the_bf16_control_fails_the_limit(name, workload):
    config = tiny_config(name)
    x, y = data.make_dataset(config, 17)
    key = jax.random.fold_in(data.stream(17, data.JOBS), 0)
    w_ref = ref.train(config, x, y, key, 50)
    w_ctrl = ref.train(config, x, y, key, 50, ref.BF16)
    assert check.rel_err(w_ctrl, w_ref) > limit(workload)


def test_surrogate_coefficients_are_the_papers_fit():
    config = tiny_config("cpml-case1-mnist37")
    cbar = ref.sigmoid_coeffs(config)
    # c0 = 1/2 at scale 2^(6+6); slope 0.1532 at 2^6
    assert list(cbar) == [2048, 10]
    assert np.array_equal(
        np.asarray(program.protocol.poly_coeffs(program.coded_config(config,
                                                                      1))),
        cbar % config["p"])


def test_base_key_uses_every_bit_of_the_seed():
    a = data.base_key(2**40 + 5)
    b = data.base_key(5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(data.base_key(7)),
                          np.asarray(jax.random.PRNGKey(7)))
