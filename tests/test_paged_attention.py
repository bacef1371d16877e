"""The paged-attention decode kernel (PR 30) on XLA:CPU, in interpret mode.

The contract under test:

* **same mathematics** — ``parallel/paged_attention.py`` over a row's
  pages, read through its table up to its frontier, agrees with
  ``transformer._cache_attention`` over the gathered capacity to the
  rounding of the pool's dtype (another order of summation, not another
  sum), at kernel-sized shapes: heads of 128, pages of 16, groups of 4;
* **exact zero weight** — what lies past a frontier (the tail of the last
  page, pages never reserved, a previous tenant's leftovers) changes no
  output bit, however large;
* **one layer of the stack** — the kernel is handed the pools of all
  layers and an index (PR 33) and reads that layer alone: the other layers
  hold large finite values in every case here;
* **it engages by what it can observe** — ``kv_pager.paged_kernel_fits``
  decides at trace time; the step it serves gives the gather path's greedy
  tokens; ``decode_kernel_steps`` says how often it ran.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorframes_tpu import observability as obs
from tensorframes_tpu.bridge.coalescer import DecodeScheduler
from tensorframes_tpu.models import decode, kv_pager
from tensorframes_tpu.models import transformer as tfm
from tensorframes_tpu.parallel import paged_attention as pa

DH, P, G = 128, 16, 4
MAX_PAGES = 10  # a capacity of 160 keys: a whole compute block and a part
CAP = MAX_PAGES * P

# what agrees "to the rounding of the pool's dtype": outputs are O(1)
TOL = {"float32": 1e-5, "bfloat16": 2**-7}


def _gathered(q, kp, vp, tables, lengths):
    """``_cache_attention`` over every row's gathered capacity: what the
    general path of ``kv_pager._paged_block`` computes."""
    B, _, dh = q.shape
    kvh = kp.shape[0]
    ck, cv = (
        jnp.moveaxis(x[:, tables], 0, 3).reshape(B, -1, kvh, dh)
        for x in (kp, vp)
    )
    return tfm._cache_attention(q[:, None], ck, cv, (lengths - 1)[:, None])[:, 0]


LAYERS = 3
POISON = 1e30  # large and finite, in bf16 as in f32


def _problem(kvh, dtype, lengths, seed=0, shuffled=True, layer=1):
    """Stacked pools, queries and tables for rows of ``lengths`` keys; a
    length of None is an idle slot (its table all trash, index 0).  The
    pools are stacks of ``LAYERS`` layers of which ``layer`` holds the
    problem and the others poison: +-1e30 everywhere."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    n_pages = B * MAX_PAGES + 1
    kp, vp = (
        jnp.full((LAYERS, kvh, n_pages, P, DH), sign * POISON, dtype)
        .at[layer].set(
            jnp.asarray(rng.standard_normal((kvh, n_pages, P, DH)), dtype)
        )
        for sign in (1, -1)
    )
    q = jnp.asarray(rng.standard_normal((B, kvh * G, DH)), dtype)
    pages = np.arange(1, n_pages)
    if shuffled:
        pages = rng.permutation(pages)
    tables = pages.reshape(B, MAX_PAGES).astype(np.int32)
    for b, n in enumerate(lengths):
        if n is None:
            tables[b] = 0
        else:  # page slots the row never reserved hold the trash page
            tables[b, kv_pager.pages_for(n, P):] = 0
    lens = np.array([n or 1 for n in lengths], np.int32)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lens)


# five rows a case, so that one interpreted kernel (ten seconds to lower)
# serves every case of a width and a dtype
ROWS = 5
CASES = {
    "one_key": [1] * ROWS,
    "under_a_page": [15] * ROWS,
    "a_page": [16] * ROWS,
    "over_a_page": [17] * ROWS,
    "full_capacity": [CAP] * ROWS,
    "mixed_lengths": [129, 1, 77, CAP, 128],
    "idle_slot_on_the_trash_page": [40, None, 9, None, 130],
}
_kernel = jax.jit(pa.paged_attention)
# the layer is traced: one lowering serves the three
WHICH_LAYER = {"first_layer": 0, "middle_layer": 1, "last_layer": LAYERS - 1}


@pytest.mark.parametrize("layer", list(WHICH_LAYER))
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kvh", [2, 8])
def test_kernel_matches_cache_attention(kvh, dtype, case, layer):
    layer = WHICH_LAYER[layer]
    q, kp, vp, tables, lengths = _problem(
        kvh, jnp.dtype(dtype), CASES[case], layer=layer
    )
    got = _kernel(q, kp, vp, tables, lengths, layer)
    want = _gathered(q, kp[layer], vp[layer], tables, lengths)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype],
    )


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernel_table_order_is_the_sequence_order(dtype):
    """A shuffled, non-contiguous table and the same pages laid out in
    order give the same bits: the kernel follows the table, not the
    pool."""
    lengths = [129, 33, CAP, 1, 16]
    q, kp, vp, tables, lens = _problem(2, jnp.dtype(dtype), lengths)
    # the same contents at pages 1, 2, 3, ... in sequence order
    flat = np.asarray(tables).reshape(-1)
    order = np.concatenate([[0], flat[flat > 0]])
    kp2, vp2 = (
        jnp.zeros_like(x).at[:, :, : len(order)].set(x[:, :, order])
        for x in (kp, vp)
    )
    renumbered = np.zeros_like(np.asarray(tables)).reshape(-1)
    renumbered[flat > 0] = np.arange(1, len(order))
    tables2 = jnp.asarray(renumbered.reshape(tables.shape))
    assert not np.array_equal(np.asarray(tables), np.asarray(tables2))
    got = _kernel(q, kp, vp, tables, lens, 1)
    same = _kernel(q, kp2, vp2, tables2, lens, 1)
    assert np.array_equal(np.asarray(got), np.asarray(same))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernel_gives_what_it_must_not_see_exact_zero_weight(dtype):
    """Unreserved pages, the trash page and the offsets past each frontier
    filled with large finite values: no output bit moves."""
    lengths = [129, 1, 77, None, 16]
    q, kp, vp, tables, lens = _problem(2, jnp.dtype(dtype), lengths)
    clean = _kernel(q, kp, vp, tables, lens, 1)
    held = np.zeros(kp.shape[2:4], bool)  # [n_pages, P]: keys some row holds
    for row, n in zip(np.asarray(tables), lengths):
        for pos in range(n or 0):
            held[row[pos // P], pos % P] = True
    assert 0 < held.sum() == sum(n or 0 for n in lengths)
    poison = jnp.asarray(~held)[None, None, :, :, None]
    big = jnp.asarray(POISON, kp.dtype)
    dirty = _kernel(
        q, jnp.where(poison, big, kp), jnp.where(poison, -big, vp), tables,
        lens, 1,
    )
    live = [b for b, n in enumerate(lengths) if n is not None]
    assert np.array_equal(np.asarray(clean)[live], np.asarray(dirty)[live])
    # the idle row attends the trash page's first key, whatever it holds
    assert bool(jnp.isfinite(dirty.astype(jnp.float32)).all())


# ---------------------------------------------------------------------------
# where it engages
# ---------------------------------------------------------------------------

FIT = tfm.TransformerConfig(
    vocab_size=97, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
    d_ff=64, max_seq=64, dtype=jnp.float32,
)
FIT_PAGE = 8  # a whole f32 sublane tile
TINY = tfm.TransformerConfig(  # tests/test_paged_decode.py's: heads of 8
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq=64, dtype=jnp.float32,
)


def _cfg(**kw):
    base = dict(
        vocab_size=97, d_model=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        d_ff=64, max_seq=64, dtype=jnp.bfloat16,
    )
    base.update(kw)
    return tfm.TransformerConfig(**base)


ZAYA_WIDTHS = dict(
    d_model=2048, n_heads=8, n_kv_heads=2,
    block=tfm.BlockSpec(
        attention="cca", ffn="experts_top1", head_dim=128, router_hidden=16
    ),
    moe_experts=4, moe_top_k=1, moe_d_ff=64,
)

# (cfg, P, B, L, pool dtype) -> fits
PREDICATE = {
    "mistral_widths": (_cfg(), 16, 12, 1, jnp.bfloat16, True),
    "zaya_widths": (_cfg(**ZAYA_WIDTHS), 16, 48, 1, jnp.bfloat16, True),
    "f32_pages_of_8": (FIT, 8, 3, 1, jnp.float32, True),
    "a_chunk_of_two": (_cfg(), 16, 12, 2, jnp.bfloat16, False),
    "a_prompt_bucket": (_cfg(), 16, 1, 128, jnp.bfloat16, False),
    "tiny_heads_of_8": (TINY, 8, 3, 1, jnp.float32, False),
    "bf16_pages_of_8": (_cfg(), 8, 12, 1, jnp.bfloat16, False),
    "pages_of_12": (_cfg(), 12, 12, 1, jnp.bfloat16, False),
    "heads_of_64": (_cfg(n_heads=64), 16, 12, 1, jnp.bfloat16, False),
    "pool_of_another_dtype": (_cfg(), 16, 12, 1, jnp.float32, False),
    "too_many_slots_for_vmem": (_cfg(), 16, 4096, 1, jnp.bfloat16, False),
}


@pytest.mark.parametrize("case", list(PREDICATE))
def test_predicate(case):
    cfg, page, B, L, dtype, fits = PREDICATE[case]
    assert kv_pager.paged_kernel_fits(cfg, page, B, L, dtype) is fits


def test_predicate_refuses_under_a_mesh_axis_left_to_partition():
    """A Mosaic kernel cannot be partitioned automatically: with heads (or
    anything) over a mesh axis the gather path runs."""
    mesh = jax.make_mesh(
        (2,), ("tp",), axis_types=(jax.sharding.AxisType.Explicit,)
    )
    args = (_cfg(), 16, 12, 1, jnp.bfloat16)
    assert kv_pager.paged_kernel_fits(*args)
    with jax.set_mesh(mesh):
        assert not kv_pager.paged_kernel_fits(*args)
    assert kv_pager.paged_kernel_fits(*args)


@pytest.fixture(scope="module")
def fit_params():
    return decode.cast_params(tfm.init(jax.random.PRNGKey(3), FIT), FIT.dtype)


def _lowered_with_kernel(cfg, params, page, slots=3):
    pool = kv_pager.PagePool(cfg, 5, tokens_per_page=page)
    text = kv_pager.paged_decode_step.lower(
        params, jnp.zeros((slots,), jnp.int32), kv_pager.init_tables(slots, 2),
        jnp.zeros((slots,), jnp.int32), pool.k_pages, pool.v_pages, cfg,
    ).as_text(debug_info=True)
    return "paged_kernel" in text, "page_gather" in text


def test_step_takes_the_kernel_where_it_fits_and_the_gather_elsewhere(fit_params):
    assert _lowered_with_kernel(FIT, fit_params, FIT_PAGE) == (True, False)
    tiny = decode.cast_params(tfm.init(jax.random.PRNGKey(0), TINY), TINY.dtype)
    assert _lowered_with_kernel(TINY, tiny, 8) == (False, True)


def test_decode_step_gives_the_gather_paths_greedy_tokens(fit_params, monkeypatch):
    """The step through the kernel against the same step with the general
    path forced (the test steers; the program has no option): the same
    greedy tokens, mixed frontiers, one slot idle."""
    slots, max_pages, steps = 4, 4, 6
    prompts = [11, 3, 16]

    def run():
        pool = kv_pager.PagePool(FIT, slots * max_pages + 1, tokens_per_page=FIT_PAGE)
        kp, vp = pool.k_pages, pool.v_pages
        tables = np.zeros((slots, max_pages), np.int32)
        toks = np.zeros((slots,), np.int32)
        for b, lp in enumerate(prompts):
            _, pages = pool.allocate(kv_pager.pages_for(lp + steps, FIT_PAGE))
            tables[b, : len(pages)] = pages
            prompt = np.random.default_rng(b).integers(0, 97, lp).astype(np.int32)
            tok0, kp, vp = kv_pager.paged_prefill(
                fit_params, jnp.asarray(prompt[None]), jnp.asarray(tables[b : b + 1]),
                jnp.asarray([lp - 1], jnp.int32), kp, vp, FIT,
            )
            toks[b] = int(tok0[0])
        indices = np.array(prompts + [0], np.int32)
        step = jax.jit(kv_pager.paged_decode_step.__wrapped__, static_argnames=("cfg",))
        out = [toks.copy()]
        for _ in range(steps):
            nxt, kp, vp = step(
                fit_params, jnp.asarray(out[-1]), jnp.asarray(tables),
                jnp.asarray(indices), kp, vp, cfg=FIT,
            )
            out.append(np.asarray(nxt))
            indices = indices + np.array([1, 1, 1, 0], np.int32)
        return np.stack(out)

    through_kernel = run()
    monkeypatch.setattr(kv_pager, "paged_kernel_fits", lambda *a: False)
    through_gather = run()
    live = slice(0, len(prompts))
    assert np.array_equal(through_kernel[:, live], through_gather[:, live])
    assert len({tuple(r) for r in through_kernel[:, live].T}) > 1  # not one stream thrice


@pytest.mark.parametrize("which", ["fitting", "tiny"])
def test_scheduler_counts_the_steps_the_kernel_ran(which, fit_params):
    cfg, page = (FIT, FIT_PAGE) if which == "fitting" else (TINY, 8)
    params = fit_params if which == "fitting" else tfm.init(jax.random.PRNGKey(0), TINY)
    sched = DecodeScheduler(
        params, cfg, max_slots=2, tokens_per_page=page, max_seq=32
    )
    before = obs.counters()
    try:
        out = sched.submit(np.arange(5, dtype=np.int32), 4)
    finally:
        sched.close()
    d = obs.counters_delta(before)
    assert len(out) == 4 and d["decode_steps"] == 3
    assert d["decode_kernel_steps"] == (3 if which == "fitting" else 0)
    assert sched.snapshot()["decode_kernel_steps"] == d["decode_kernel_steps"]
    assert "tfs_decode_kernel_steps_total" in obs.metrics_text()


def test_serving_imports_pallas_without_its_gpu_interpreter():
    """A process that serves decode imports the kernel in its set-up; most
    of what that import costs is Pallas's GPU interpreter, which
    ``tensorframes_tpu.parallel`` leaves out (a fresh interpreter: here
    Pallas is long imported)."""
    code = (
        "import sys\n"
        "from tensorframes_tpu.models import kv_pager\n"
        "from tensorframes_tpu.parallel import flash, paged_attention\n"
        "assert 'jax._src.pallas.pallas_call' in sys.modules\n"
        "assert 'jax._src.pallas.mosaic.lowering' in sys.modules\n"
        "assert 'jax.experimental.mosaic.gpu' not in sys.modules\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    done = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]


# ---------------------------------------------------------------------------
# a window over a ring of pages (a window layer of a stack whose attention
# differs by layer): the table is the slot's ring, logical page p in slot
# p % ring, and the row attends to its last ``window`` keys
# ---------------------------------------------------------------------------

RING_WINDOW = 40  # keys: two and a half pages of 16
RING = kv_pager.ring_pages(tfm.TransformerConfig(block=tfm.BlockSpec(
    layer_types=("window",), window=RING_WINDOW), n_layers=1), P)


def _ring_problem(kvh, dtype, lengths, seed=3, layer=1):
    """Stacked pools whose rows hold rings of ``RING`` pages; ``lengths``
    the rows' keys, far past the window for most.  Poison in other
    layers, as in :func:`_problem`."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    n_pages = B * RING + 1
    kp, vp = (
        jnp.full((LAYERS, kvh, n_pages, P, DH), sign * POISON, dtype)
        .at[layer].set(
            jnp.asarray(rng.standard_normal((kvh, n_pages, P, DH)), dtype)
        )
        for sign in (1, -1)
    )
    q = jnp.asarray(rng.standard_normal((B, kvh * G, DH)), dtype)
    tables = rng.permutation(np.arange(1, n_pages)).reshape(B, RING)
    return q, kp, vp, jnp.asarray(tables.astype(np.int32)), jnp.asarray(
        np.asarray(lengths, np.int32)
    )


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_window_kernel_reads_the_ring_under_the_window(dtype):
    """Rows short of the window, at it, and several rings deep: the kernel
    against ``_cache_attention`` over the ring's gathered keys at their
    sequence positions under the window's mask (the gather path of a window
    layer), and against nothing of the keys before the window."""
    lengths = [1, 30, RING_WINDOW, 41, 130, 1000, 16 * RING]
    q, kp, vp, tables, lens = _ring_problem(2, jnp.dtype(dtype), lengths)
    got = jax.jit(pa.paged_attention, static_argnames="window")(
        q, kp, vp, tables, lens, 1, window=RING_WINDOW
    )
    B = len(lengths)
    ck, cv = (
        jnp.moveaxis(x[1][:, tables], 0, 3).reshape(B, -1, 2, DH) for x in (kp, vp)
    )
    pos = (lens - 1)[:, None]
    k_pos = kv_pager._ring_positions(pos, P, RING)
    want = tfm._cache_attention(q[:, None], ck, cv, pos, RING_WINDOW, k_pos)[:, 0]
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype],
    )
    # what the window left behind is not read: poison the ring's slots that
    # hold no key of the row's window, and nothing moves
    poisoned = np.asarray(kp).copy()
    for b, n in enumerate(lengths):
        keep = {(t // P) % RING for t in range(max(0, n - RING_WINDOW), n)}
        for s in set(range(RING)) - keep:
            poisoned[1, :, int(tables[b, s])] = POISON
    again = jax.jit(pa.paged_attention, static_argnames="window")(
        q, jnp.asarray(poisoned), vp, tables, lens, 1, window=RING_WINDOW
    )
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_call_without_a_window_is_the_kernel_it_was(dtype):
    """No window is the call without one, program text for program text
    (``tests/test_paged_compile.py`` pins the serving steps' text, kernel
    and all, to the tree before windows), and a window as long as the
    capacity over the same table reads the same pages in the same blocks,
    bit for bit."""
    q, kp, vp, tables, lengths = _problem(8, jnp.dtype(dtype), CASES["mixed_lengths"])
    kernel = jax.jit(pa.paged_attention, static_argnames="window")
    plain = kernel.lower(q, kp, vp, tables, lengths, 1).as_text()
    assert plain == kernel.lower(q, kp, vp, tables, lengths, 1, window=0).as_text()
    assert plain != kernel.lower(q, kp, vp, tables, lengths, 1, window=CAP).as_text()
    got = _kernel(q, kp, vp, tables, lengths, 1)
    wide = jax.jit(pa.paged_attention, static_argnames="window")(
        q, kp, vp, tables, lengths, 1, window=CAP
    )
    np.testing.assert_array_equal(np.asarray(wide), np.asarray(got))
