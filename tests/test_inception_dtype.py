"""The Inception scoring program runs in the type it is given (PR 36).

One strongly typed scalar (``bf16 / np.float32(127.5)``) once promoted the
whole network to float32: every activation was stored and re-read at four
bytes while the MXU rounded the operands to bf16 anyway.  The guards here
read the *trace* (``jax.make_jaxpr``: nothing compiles, nothing runs) and
then, at a few rows on the CPU, hold the bf16 program to the benchmark's
float32 reference by the benchmark's own distance.  The compiled module's
bytes are ``tests/test_paged_compile.py``'s to guard.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402
from perfbench.drivers.frame_score import Driver  # noqa: E402
from perfbench.refs import inception_v3 as ref  # noqa: E402
from tensorframes_tpu.models import inception  # noqa: E402

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
ROWS = 2  # of the traced block

# what may produce, and what may read, a float32 value of rank 4: a
# convolution's accumulator, the elementwise epilogue on it, the upcast a
# summing reduce reads, and the one cast down.  Never a convolution's
# operand, a concatenation, a max-pool: those are the stored activations.
_F32_PRODUCERS = {
    "conv_general_dilated", "add", "sub", "mul", "div", "max",
    "convert_element_type", "reduce_window_sum",
}
_F32_READERS = _F32_PRODUCERS - {"conv_general_dilated"} | {"reduce_sum"}


def _weights(form, dtype):
    """The two trees the program is handed: the benchmark's folded-bias tree
    (as traced arguments) and ``init``'s unfolded scale / shift."""
    if form == "bias":
        return ref.make_weights(0, dtype)
    return inception.init(0, dtype=dtype)


def _trace(form, dtype):
    weights = _weights(form, dtype)
    image = jax.ShapeDtypeStruct((ROWS, inception.INPUT_SIZE**2 * 3), jnp.uint8)
    return jax.make_jaxpr(
        lambda w, x: inception.scoring_program(w, dtype=dtype, fold=False)(x)
    )(weights, image).jaxpr


def _subjaxprs(eqn):
    for v in eqn.params.values():
        inner = getattr(v, "jaxpr", v)
        if hasattr(inner, "eqns"):
            yield inner


def _walk(jaxpr):
    """Every equation, those of nested calls (``relu`` is one) included,
    each with the equation that made each of its operands in its own
    jaxpr."""
    made = {}
    for eqn in jaxpr.eqns:
        yield eqn, [made.get(id(v)) for v in eqn.invars]
        for out in eqn.outvars:
            made[id(out)] = eqn
        for inner in _subjaxprs(eqn):
            yield from _walk(inner)


def _is(var, dtype, rank=None):
    """``var`` has ``dtype``; with ``rank`` 4, and is an activation: one
    image a row (a broadcast bias or a pool's counts are rank 4 too)."""
    aval = getattr(var, "aval", None)
    if aval is None or getattr(aval, "dtype", None) != dtype:
        return False
    return rank is None or (len(aval.shape) == rank and aval.shape[0] == ROWS)


@pytest.mark.parametrize("form", ["bias", "scale_shift"])
def test_bf16_program_stores_every_activation_in_bf16(form):
    convs = dots = casts_down = 0
    for eqn, makers in _walk(_trace(form, BF16)):
        name = eqn.primitive.name
        call = any(True for _ in _subjaxprs(eqn))
        if name == "conv_general_dilated":
            convs += 1
            assert all(_is(v, BF16) for v in eqn.invars), eqn
            assert _is(eqn.outvars[0], F32)  # the accumulator
        if name == "dot_general":
            dots += 1
            assert all(_is(v, BF16) for v in eqn.invars), eqn
            assert _is(eqn.outvars[0], F32)  # the logits
        if name == "argmax":
            assert _is(eqn.invars[0], F32)  # and they stay float32
        if name in ("concatenate", "reduce_window_max"):
            assert all(_is(v, BF16) for v in (*eqn.invars, *eqn.outvars)), eqn
        if not call:
            if any(_is(v, F32, 4) for v in eqn.outvars):
                assert name in _F32_PRODUCERS, eqn
            if any(_is(v, F32, 4) for v in eqn.invars):
                assert name in _F32_READERS, eqn
        if name in ("reduce_window_sum", "reduce_sum") and _is(eqn.invars[0], F32, 4):
            # the float32 copy is made for the sum alone, from a bf16 value
            made = makers[0]
            assert made is not None and made.primitive.name == "convert_element_type"
            assert _is(made.invars[0], BF16, 4), made
        if name == "convert_element_type" and _is(eqn.outvars[0], BF16, 4):
            casts_down += 1
    assert convs == 94 and dots == 1
    # one rounding a layer: 94 convolutions, 9 average pools, the image
    assert casts_down == 94 + 9 + 1


@pytest.mark.parametrize("form", ["bias", "scale_shift"])
def test_f32_program_holds_no_bf16_at_all(form):
    convs = 0
    for eqn, _ in _walk(_trace(form, F32)):
        convs += eqn.primitive.name == "conv_general_dilated"
        assert not any(_is(v, BF16) for v in (*eqn.invars, *eqn.outvars)), eqn
    assert convs == 94


@pytest.mark.parametrize("seed", [11, 3600000101])
def test_bf16_program_against_the_float32_reference(seed):
    """The benchmark's own images, weights, reference, control and distance
    (the cell's driver at its ``tiny`` sizes), on the first four rows."""
    _, cell, config, traffic = run.load_cell("inception_v3.score_cached", rehearse=True)
    driver = Driver(run.context(cell, config, traffic, seed))
    driver.weights, driver.images = ref.make_weights(seed, BF16), driver._images()
    idx = np.arange(4)
    # weights as arguments, as the benchmark hands them (and nothing to fold)
    got = jax.device_get(jax.jit(
        lambda w, x: inception.scoring_program(w, dtype=BF16, fold=False)(x)
    )(driver.weights, driver.images[idx]))
    exact, control = driver.reference(idx), driver.reference(idx, "fp8")
    gap = Driver.gaps(got["prediction"], got["score"], exact)["answer_rms_gap"]
    control_gap = Driver.gaps(control[0], control[1], exact)["answer_rms_gap"]
    limit = config["limits"]["answer_rms_gap"]
    assert gap < limit and gap < control_gap / 2, (gap, control_gap)
    best_two = np.sort(exact[2], axis=-1)[:, -2:]
    decided = best_two[:, 1] - best_two[:, 0] > limit
    assert np.array_equal(got["prediction"][decided], exact[0][decided])


# one block of each kind with an average-pool branch, at its place in the
# network: (variant, kwargs, grid, channels in)
_POOLED_BLOCKS = {
    "mixed0_A": ("A", {"pool_ch": 32}, 35, 192),
    "mixed4_C": ("C", {"c7": 128}, 17, 768),
    "mixed9_E": ("E", {}, 8, 1280),
}


def _pool_first(ps, x):
    """The pool branch in the published graph's order: the 3x3 SAME average
    pool of the block input (padding left out of the mean, the counts
    summed here from ones), rounded to ``x``'s type, then the 1x1
    convolution."""
    win = ((1, 3, 3, 1), (1, 1, 1, 1), "SAME")
    s = jax.lax.reduce_window(x.astype(F32), 0.0, jax.lax.add, *win)
    ones = jnp.ones((1, *x.shape[1:3], 1), F32)
    n = jax.lax.reduce_window(ones, 0.0, jax.lax.add, *win)
    return inception._conv(ps[0], (s / n).astype(x.dtype))


def _pooled_block(block, form, seed=7):
    """Float32 params of one block, batch norm drawn so that the
    pre-activations take both signs (folded to a bias for ``"bias"``), and
    an input of two rows of N(0, 1)."""
    variant, kw, grid, cin = _POOLED_BLOCKS[block]
    rng = np.random.RandomState(seed)
    params = inception._block_init(rng, variant, cin, np.float32, **kw)
    for p in (p for branch in params.values() for p in branch):
        p["scale"] = rng.uniform(0.5, 1.5, p["scale"].shape).astype(np.float32)
        p["shift"] = rng.normal(0.0, 0.5, p["shift"].shape).astype(np.float32)
    if form == "bias":
        params = inception.fold_bn({"stem": [], "blocks": [params]})["blocks"][0]
    x = rng.normal(size=(2, grid, grid, cin)).astype(np.float32)
    return params, x


def _rms(a):
    return float(np.sqrt(np.mean(np.square(a))))


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("form", ["bias", "scale_shift"])
@pytest.mark.parametrize("block", list(_POOLED_BLOCKS))
def test_pool_branch_convolves_then_pools(block, form, dtype, monkeypatch):
    """The average pool commutes with the 1x1 convolution (its weight,
    1 / count(position), is the same in every channel), so the branch may
    pool the convolution's narrow output.  In float32 the whole block
    equals the pool-first order; in bf16 the branch rounds twice, as the
    pool-first order does, and not three times."""
    variant, kw, _, _ = _POOLED_BLOCKS[block]
    params, x = _pooled_block(block, form)
    assert (np.asarray(_pool_first(params["pool"], jnp.asarray(x))) == 0).any()
    if dtype == F32:
        got = np.asarray(inception._block_apply(params, x, variant, **kw))
        monkeypatch.setattr(inception, "_pool_branch", _pool_first)
        want = np.asarray(inception._block_apply(params, x, variant, **kw))
        np.testing.assert_allclose(
            got, want, rtol=1e-5, atol=1e-6 * np.abs(want).max()
        )
        return
    ps = [jax.tree.map(lambda a: jnp.asarray(a, BF16), p) for p in params["pool"]]
    xb = jnp.asarray(x, BF16)
    out = jax.eval_shape(inception._pool_branch, ps, xb)
    casts = [
        eqn for eqn, _ in _walk(jax.make_jaxpr(inception._pool_branch)(ps, xb).jaxpr)
        if eqn.primitive.name == "convert_element_type"
        and eqn.outvars[0].aval.dtype == BF16
        and eqn.outvars[0].aval.shape == out.shape
    ]
    assert len(casts) == 2, casts  # the raw convolution, the branch's output
    # each order's distance from the float32 branch on the same bf16 values:
    # a third rounding (the pool's mean stored) reads 1.15-1.23 x pool-first
    exact = np.asarray(inception._pool_branch(
        [jax.tree.map(lambda a: a.astype(F32), p) for p in ps], xb.astype(F32)
    ))
    gap = {
        name: _rms(np.asarray(f(ps, xb), np.float32) - exact) / _rms(exact)
        for name, f in (("new", inception._pool_branch), ("old", _pool_first))
    }
    assert gap["new"] < 1.08 * gap["old"], gap
