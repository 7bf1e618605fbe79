"""The references' own bookkeeping."""
import numpy as np

from perfbench import traffic
from perfbench.references import dense_lm
from perfbench.tests import tiny


def test_weights_are_a_function_of_the_seed_leaf_by_leaf():
    cfg = tiny.train_config()
    seed = 2**33 + 3                      # more than 32 bits
    p = dense_lm.init_params(cfg, seed)
    # the initial value is made again leaf by leaf, by the same function
    # compiled alone; the two may differ in the last bits only
    size = dense_lm.leaf_norms(p)
    assert all(v <= 1e-6 * max(size[n], 1.0) for n, v in
               dense_lm.change_norms(p, cfg, seed).items())
    q = dense_lm.init_params(cfg, seed + 1)
    assert any(v > 0 for v in dense_lm.change_norms(q, cfg, seed).values())


def test_batches_differ_by_step_and_repeat_by_seed():
    a = traffic.token_batch(2**31 + 1, 1, 2, 16, 100)
    b = traffic.token_batch(2**31 + 1, 2, 2, 16, 100)
    c = traffic.token_batch(2**31 + 1, 1, 2, 16, 100)
    assert not np.array_equal(a["tokens"], b["tokens"])
    assert np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"][0], a["tokens"][1])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
