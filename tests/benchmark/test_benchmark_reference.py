"""The plain reference against the program at a tiny width on the CPU, its
lower-precision control, and the weights both are given."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.arch import llama
from benchmark.lib import weights
from benchmark.reference import mistral

import _tiny

CFG = dict(llama.tiny({}), rope_theta=1e6, rms_norm_eps=1e-5)
SHAPES = llama.shapes(CFG)

with open(os.path.join(os.path.dirname(__file__),
                       "recorded_weights_tiny.json")) as f:
    RECORDED = json.load(f)


@pytest.fixture(scope="module")
def w():
    return weights.make_weights(SHAPES, 2**31 + 7, "float32")


def test_weights_depend_on_the_seed_alone(w):
    again = weights.make_weights(SHAPES, 2**31 + 7, "float32")
    other = weights.make_weights(SHAPES, 2**31 + 8, "float32")
    one = weights.make_weights(SHAPES, 2**31 + 7, "float32", ["norm.weight"])
    assert all((w[n] == again[n]).all() for n in w)
    assert all((w[n] != other[n]).any() for n in w)
    assert (one["norm.weight"] == w["norm.weight"]).all()
    assert set(w) == set(SHAPES)
    assert abs(float(jnp.std(w["lm_head.weight"])) - 0.02) < 1e-3
    assert abs(float(jnp.mean(w["norm.weight"])) - 1.0) < 0.05


@pytest.mark.parametrize("name", sorted(RECORDED["leaves"]))
def test_weights_are_what_they_were_before_an_architecture_gave_the_leaves(name):
    """Each leaf of both configurations (tiny widths, their own depth and
    dtype) against sums recorded from the parent of PR 27: the same leaves
    in the same order, the same shapes, the same values."""
    with open(os.path.join(_tiny.ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    own = {k: cfg[k] for k in ("num_hidden_layers", "dtype")}
    cfg.update(llama.tiny({}), **own)
    shapes = llama.shapes(cfg)
    w = weights.make_weights(shapes, RECORDED["seed"], cfg["dtype"])
    want = RECORDED["leaves"][name]
    assert list(shapes) == [leaf[0] for leaf in want]
    for leaf, shape, dtype, total, squares in want:
        a = np.asarray(w[leaf].astype(jnp.float32)).astype(np.float64)
        assert (list(a.shape), str(w[leaf].dtype)) == (shape, dtype), leaf
        assert a.sum() == pytest.approx(total, rel=1e-9, abs=1e-9), leaf
        assert (a * a).sum() == pytest.approx(squares, rel=1e-9), leaf


def test_reference_agrees_with_llama_for_causal_lm(w):
    import paddle_tpu

    model = llama.build_model(CFG, 64)
    for n, p in model.named_parameters():
        p._value = w[n]
    tok = np.random.RandomState(0).randint(0, CFG["vocab_size"], (2, 48))
    got = np.asarray(model(paddle_tpu.to_tensor(tok))._value)
    ref = np.asarray(mistral.logits(CFG, w, jnp.asarray(tok)))
    assert np.abs(ref).max() > 0.5
    assert np.abs(got - ref).max() < 1e-5


def test_reference_is_causal_and_uses_every_part(w):
    tok = np.random.RandomState(1).randint(0, CFG["vocab_size"], (1, 32))
    ref = np.asarray(mistral.logits(CFG, w, jnp.asarray(tok)))
    later = tok.copy()
    later[0, 20:] = (later[0, 20:] + 1) % CFG["vocab_size"]
    moved = np.asarray(mistral.logits(CFG, w, jnp.asarray(later)))
    assert np.abs(moved[0, :20] - ref[0, :20]).max() == 0.0
    assert np.abs(moved[0, 20:] - ref[0, 20:]).max() > 1e-3
    # each part of the architecture moves the answer: theta, eps, GQA map
    for key, val in (("rope_theta", 1e4), ("rms_norm_eps", 1e-2)):
        other = np.asarray(mistral.logits(dict(CFG, **{key: val}), w,
                                          jnp.asarray(tok)))
        assert np.abs(other - ref).max() > 1e-4, key


def test_int8_control_is_close_but_not_equal(w):
    tok = np.random.RandomState(2).randint(0, CFG["vocab_size"], (2, 32))
    ref = np.asarray(mistral.logits(CFG, w, jnp.asarray(tok)))
    low = np.asarray(mistral.logits(CFG, w, jnp.asarray(tok),
                                    mistral.int8_linear))
    err = np.abs(low - ref).max()
    assert 1e-4 < err < 0.2


def test_adamw_agrees_with_the_program(w):
    from paddle_tpu.optimizer import AdamW

    p = w["norm.weight"]
    g = jnp.linspace(-1e-3, 1e-3, p.size, dtype=jnp.float32)
    opt = AdamW(learning_rate=1e-4, beta1=0.9, beta2=0.999, epsilon=1e-8,
                weight_decay=0.01)
    st = opt.init_state_tree({"p": p})
    got, st = opt.apply_gradients({"p": p}, {"p": g}, st, 1e-4)
    got2, _ = opt.apply_gradients(got, {"p": 2 * g}, st, 1e-4)
    m = v = jnp.zeros_like(p)
    r1, m, v = mistral.adamw_step(p, g, m, v, 1, 1e-4)
    r2, m, v = mistral.adamw_step(r1, 2 * g, m, v, 2, 1e-4)
    assert np.abs(np.asarray(got["p"] - r1)).max() < 1e-7
    assert np.abs(np.asarray(got2["p"] - r2)).max() < 1e-7
    assert np.abs(np.asarray(r2 - p)).max() > 1e-4


def test_loss_sum_and_grads_match_autodiff_of_logits(w):
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randint(0, CFG["vocab_size"], (2, 16)))
    y = jnp.asarray(rng.randint(0, CFG["vocab_size"], (2, 16)))

    def plain(w):
        lp = jax.nn.log_softmax(mistral.logits(CFG, w, x), -1)
        return -jnp.sum(jnp.take_along_axis(lp, y[..., None], -1))

    a, ga = jax.value_and_grad(plain)(w)
    b, gb = jax.value_and_grad(lambda w: mistral.loss_sum(CFG, w, x, y))(w)
    assert abs(float(a - b)) < 1e-3 * abs(float(a))
    for n in w:
        assert np.abs(np.asarray(ga[n] - gb[n])).max() <= \
            1e-4 * max(np.abs(np.asarray(ga[n])).max(), 1e-6), n
