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
