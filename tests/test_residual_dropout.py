"""``ResidualDropout`` (models/transformer.py): the block's two dropout
sites draw each keep-mask once a call, as an array of its own.

What must hold: the same threefry Bernoulli at the published rate with the
``x / (1 - rate)`` scaling; an independent mask for every site, layer, step
and microbatch; a backward that reads the forward's mask and draws nothing;
no rng and no barrier at rate 0 or outside training; counters that agree
with the mask. That the TPU compiler then keeps the bit generation out of
the GEMM fusions is a fact of the optimized program: PERF.md section 6,
PR 34 (compiled for a described v5e and read on the chip).
"""

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dct_tpu.config import ModelConfig
from dct_tpu.models.registry import get_model
from dct_tpu.models.transformer import ResidualDropout
from dct_tpu.train.steps import counter_metrics

RATE = 0.1
SHAPE = (2, 512, 256)
#: Two layers of the causal family, small enough to trace in a second.
CFG = dict(
    name="weather_transformer_causal", seq_len=16, d_model=32, n_heads=2,
    n_layers=2, d_ff=64, dropout=RATE,
)
X_SHAPE = (4, CFG["seq_len"], 5)
MASK_SIZE = X_SHAPE[0] * CFG["seq_len"] * CFG["d_model"]
SITES = [
    (f"block_{layer}", site)
    for layer in range(CFG["n_layers"]) for site in ("drop_attn", "drop_ffn")
]


def _site(x, key):
    """One training call of one site: its output and what it sowed."""
    return ResidualDropout(RATE).apply(
        {}, x, True, rngs={"dropout": key},
        mutable=["intermediates", "counters"],
    )


def _x(shape=SHAPE, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _model(**over):
    model = get_model(ModelConfig(**{**CFG, **over}), input_dim=X_SHAPE[-1])
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1,) + X_SHAPE[1:]))
    return model, {"params": params["params"]}


def _masks(model, params, key, x=None):
    """Every site's mask of one training call, by (layer, site)."""
    x = _x(X_SHAPE, 3) if x is None else x
    _, sown = model.apply(
        params, x, train=True, rngs={"dropout": key},
        mutable=["intermediates"],
    )
    return {
        s: np.asarray(sown["intermediates"][s[0]][s[1]]["keep"][0])
        for s in SITES
    }


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _mask_draws(closed) -> int:
    """Bit-generation calls whose output has a mask's element count (the
    key folding draws a handful of words, never that many)."""
    return sum(
        1 for e in _eqns(closed.jaxpr)
        if e.primitive.name in ("random_bits", "threefry2x32")
        and math.prod(e.outvars[0].aval.shape) == MASK_SIZE
    )


# (a) ---------------------------------------------------------------------
def test_one_site_drops_at_the_rate_and_scales_what_it_keeps():
    x = _x()
    out, sown = _site(x, jax.random.PRNGKey(7))
    keep = np.asarray(sown["intermediates"]["keep"][0])
    assert keep.dtype == np.bool_ and keep.shape == SHAPE
    n = keep.size
    sigma = math.sqrt(RATE * (1 - RATE) / n)
    assert abs(1 - keep.mean() - RATE) < 3 * sigma
    np.testing.assert_array_equal(
        np.asarray(out), np.where(keep, np.asarray(x) / np.float32(0.9), 0))


def test_one_site_keeps_the_compute_dtype():
    out, _ = _site(_x().astype(jnp.bfloat16), jax.random.PRNGKey(7))
    assert out.dtype == jnp.bfloat16


# (b) ---------------------------------------------------------------------
def test_masks_of_sites_layers_steps_and_microbatches_differ_pairwise():
    model, params = _model()
    rng = jax.random.PRNGKey(11)
    # train/steps.py: the step's key is the state's folded by the step, a
    # microbatch's that folded by its index.
    keys = {
        "step0": jax.random.fold_in(rng, 0),
        "step1": jax.random.fold_in(rng, 1),
        "step1.micro0": jax.random.fold_in(jax.random.fold_in(rng, 1), 0),
        "step1.micro1": jax.random.fold_in(jax.random.fold_in(rng, 1), 1),
    }
    masks = {
        (name, *site): m
        for name, key in keys.items()
        for site, m in _masks(model, params, key).items()
    }
    assert len(masks) == 16
    for (a, ma), (b, mb) in itertools.combinations(masks.items(), 2):
        # Independent masks at rate 0.1 agree on 82% of elements.
        agree = (ma == mb).mean()
        assert 0.75 < agree < 0.89, (a, b, agree)
    # And the same key gives the same masks: the draw is the key's alone.
    again = _masks(model, params, keys["step0"], x=_x(X_SHAPE, 5))
    for site in SITES:
        np.testing.assert_array_equal(again[site], masks[("step0", *site)])


# (c) ---------------------------------------------------------------------
def test_the_backward_reads_the_mask_the_forward_used():
    x = _x()

    def total(x):
        out, sown = _site(x, jax.random.PRNGKey(7))
        return out.sum(), sown["intermediates"]["keep"][0]

    (_, keep), grad = jax.value_and_grad(total, has_aux=True)(x)
    np.testing.assert_array_equal(
        np.asarray(grad),
        np.where(np.asarray(keep), np.float32(1) / np.float32(0.9), 0))


# (d) ---------------------------------------------------------------------
@pytest.mark.parametrize(
    "rate, train", [(0.0, True), (RATE, False), (0.0, False)],
    ids=["rate0", "eval", "rate0_eval"])
def test_no_rng_and_no_barrier_where_nothing_is_dropped(rate, train):
    x = _x((2, 16, 8))
    # No rng is handed over: asking for one would raise.
    out = ResidualDropout(rate).apply({}, x, train)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    text = str(jax.make_jaxpr(
        lambda x: ResidualDropout(rate).apply({}, x, train))(x))
    for name in ("random_", "threefry2x32", "optimization_barrier"):
        assert name not in text


@pytest.mark.parametrize("train", [True, False], ids=["train", "eval"])
def test_a_model_at_rate_0_traces_no_rng_and_no_barrier(train):
    model, params = _model(dropout=0.0)
    text = str(jax.make_jaxpr(
        lambda p, x: model.apply(p, x, train=train))(params, _x(X_SHAPE)))
    for name in ("random_bits", "threefry2x32", "optimization_barrier"):
        assert name not in text


# (e) ---------------------------------------------------------------------
def _loss_fn(model):
    def loss(params, x, key):
        logits, sown = model.apply(
            params, x, train=True, rngs={"dropout": key},
            mutable=["counters"])
        return (logits ** 2).mean(), sown

    return loss


def test_a_step_draws_one_mask_a_site_all_in_the_forward():
    model, params = _model()
    args = (params, _x(X_SHAPE), jax.random.PRNGKey(1))
    loss = _loss_fn(model)
    forward = jax.make_jaxpr(loss)(*args)
    step = jax.make_jaxpr(jax.value_and_grad(loss, has_aux=True))(*args)
    assert _mask_draws(forward) == len(SITES) == 4
    # The step holds the forward's four and no more: none in the backward.
    assert _mask_draws(step) == 4
    barriers = [
        e for e in _eqns(step.jaxpr)
        if e.primitive.name == "optimization_barrier"]
    assert len(barriers) == 4
    assert all(v.aval.dtype == jnp.bool_ for e in barriers for v in e.invars)


def test_under_remat_the_backward_draws_each_mask_once_more():
    """What the design gives with ``remat=True`` (no cell runs it): the
    mask is drawn INSIDE the block, so the rematerialised backward draws it
    once more, from the same key and behind the same barrier; it is still
    no expression a GEMM could absorb."""
    model, params = _model(remat=True)
    args = (params, _x(X_SHAPE), jax.random.PRNGKey(1))
    loss = _loss_fn(model)
    assert _mask_draws(jax.make_jaxpr(loss)(*args)) == 4
    step = jax.make_jaxpr(jax.value_and_grad(loss, has_aux=True))(*args)
    assert _mask_draws(step) == 8
    # Same key, same mask: the gradients equal the plain model's.
    plain, _ = _model()
    g_remat = jax.grad(loss, has_aux=True)(*args)[0]
    g_plain = jax.grad(_loss_fn(plain), has_aux=True)(*args)[0]
    for a, b in zip(jax.tree.leaves(g_remat), jax.tree.leaves(g_plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# (f) ---------------------------------------------------------------------
def test_the_counters_agree_with_the_mask():
    _, sown = _site(_x(), jax.random.PRNGKey(7))
    keep = np.asarray(sown["intermediates"]["keep"][0])
    counted = counter_metrics(sown["counters"])
    assert counted == {
        "dropout_kept": float(keep.sum()), "dropout_total": float(keep.size)}


def test_a_models_counters_sum_over_its_sites():
    model, params = _model()
    key = jax.random.PRNGKey(5)
    x = _x(X_SHAPE, 3)
    _, sown = model.apply(
        params, x, train=True, rngs={"dropout": key}, mutable=["counters"])
    counted = counter_metrics(sown["counters"])
    masks = _masks(model, params, key)
    assert counted["dropout_total"] == 4 * MASK_SIZE
    assert counted["dropout_kept"] == sum(int(m.sum()) for m in masks.values())


def test_no_counter_where_nothing_is_dropped():
    model, params = _model(dropout=0.0)
    _, sown = model.apply(
        params, _x(X_SHAPE), train=True, mutable=["counters"])
    assert not jax.tree.leaves(sown)
