"""The paged kernel's latent form (``tfs_latent_attention``) on XLA:CPU, in
interpret mode, and the gate that sends a latent block's decode step to it.

The contract under test:

* **same mathematics** — ``paged_attention.latent_attention`` over a row's
  pages, read through its table up to its frontier, between
  ``mla.absorb`` and ``mla.up``, agrees with ``mla.attend_absorbed`` over
  the gathered capacity to the rounding of the pool's dtype, with the
  block's own softmax scale (YaRN's ``m^2`` in it or not);
* **exact zero weight and one layer of the stack** — what lies past a
  frontier, and the other layers of the stacked pool (large finite values
  here), change no output;
* **it engages by what it can observe** — ``kv_pager.latent_kernel_fits``
  takes the A.X-K1 serving shape and refuses a chunk, a width that is not
  whole lane tiles, a pool of another dtype and a mesh axis.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as bench_run  # noqa: E402
from perfbench.drivers.bridge_decode_axk1 import transformer_config  # noqa: E402
from perfbench.refs import axk1_decoder as ref  # noqa: E402
from tensorframes_tpu.models import kv_pager, mla  # noqa: E402
from tensorframes_tpu.parallel import paged_attention as pa  # noqa: E402

P, MAX_PAGES, LAYERS = 16, 12, 3
CAP = MAX_PAGES * P  # 192 keys: a whole compute block and a part
POISON = 1e30  # the layers not asked for: large and finite, in bf16 as in f32
# what agrees "to the rounding of the pool's dtype" (the GQA kernel's tests)
TOL = {"float32": 1e-5, "bfloat16": 2**-7}


def _file():
    with open(os.path.join(ROOT, "perfbench", "configs", "axk1_l7_ep16.json")) as f:
        return json.load(f)


def _model(scale):
    """The tiny A.X-K1 file with a latent of one lane tile (rows of 256:
    128 + 8 rotated, zeros after), YaRN's ``m^2`` on the scores or not."""
    m = _file()
    m = bench_run.overlay(m, m["tiny"])
    m = bench_run.overlay(m, {"kv_lora_rank": 128})
    if scale == "plain":
        m = bench_run.overlay(m, {"rope_scaling": {"mscale_all_dim": 0}})
    return m


SCALES = ("yarn", "plain")


@pytest.fixture(scope="module")
def blocks():
    """(cfg, one layer's params) a scale, in each dtype."""
    out = {}
    for scale in SCALES:
        m = _model(scale)
        for dtype in ("float32", "bfloat16"):
            dt = jnp.dtype(dtype)
            cfg = transformer_config(m, CAP, dt)
            w = ref.make_weights(5, m, dt)
            out[scale, dtype] = cfg, jax.tree_util.tree_map(lambda a: a[1], w["blocks"])
    return out


def _problem(cfg, lengths, layer, seed=0):
    """The stacked pool, the absorbed query's parts and the tables for rows
    of ``lengths`` tokens; a length of None is an idle slot (its table all
    trash, index 0).  ``layer`` holds the rows, the others poison."""
    rng = np.random.default_rng(seed)
    B, dt = len(lengths), cfg.dtype
    width, lat = mla.row_width(cfg), cfg.block.latent
    n_pages = B * MAX_PAGES + 1
    rows = np.zeros((n_pages, P, width), np.float32)
    rows[..., : mla.page_width(cfg)] = rng.standard_normal((n_pages, P, mla.page_width(cfg)))
    pages = jnp.full((LAYERS, 1, n_pages, P, width), POISON, dt).at[layer, 0].set(
        jnp.asarray(rows, dt)
    )
    q_n = jnp.asarray(rng.standard_normal((B, 1, cfg.n_heads, lat.nope_dim)), dt)
    q_r = jnp.asarray(rng.standard_normal((B, 1, cfg.n_heads, lat.rope_dim)), dt)
    # the pages scattered over the pool out of order
    tables = rng.permutation(np.arange(1, n_pages)).reshape(B, MAX_PAGES).astype(np.int32)
    for b, n in enumerate(lengths):
        if n is None:
            tables[b] = 0
        else:  # page slots the row never reserved hold the trash page
            tables[b, kv_pager.pages_for(n, P):] = 0
    lens = np.array([n or 1 for n in lengths], np.int32)
    return pages, q_n, q_r, jnp.asarray(tables), jnp.asarray(lens)


def _through_kernel(bp, cfg, pages, q_n, q_r, tables, lengths, layer, static_layer):
    """absorb -> the kernel -> up, the layer a traced scalar or a Python
    int baked into the trace."""
    q = mla.absorb(bp, q_n, q_r, pages.shape[-1], cfg)[:, 0]
    kernel = jax.jit(
        pa.latent_attention, static_argnums=(4, 5, 6) if static_layer else (5, 6)
    )
    if not static_layer:
        layer = jnp.int32(layer)
    o_lat = kernel(
        q, pages, tables, lengths, layer, cfg.block.latent.kv_rank, mla.softmax_scale(cfg)
    )
    assert o_lat.shape == (q.shape[0], cfg.n_heads, cfg.block.latent.kv_rank)
    assert o_lat.dtype == cfg.dtype
    return mla.up(bp, o_lat[:, None], cfg)


def _gathered(bp, cfg, pages, q_n, q_r, tables, lengths, layer):
    """``mla.attend_absorbed`` over every row's gathered capacity: the
    gather path of ``kv_pager._Mla.step``, in float32 over the
    same values (XLA:CPU runs no batched bfloat16 product into float32,
    which the weighted sum of rows is)."""
    B = tables.shape[0]
    f32 = dataclasses.replace(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    up = lambda x: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), x)  # noqa: E731
    rows = pages[layer, 0, tables].reshape(B, CAP, -1)
    return mla.attend_absorbed(
        up(bp), up(q_n), up(q_r), up(rows), (lengths - 1)[:, None], f32
    )


CASES = {
    "one_key": [1] * 4,
    "under_a_page": [P - 1] * 4,
    "a_page": [P] * 4,
    "over_a_page": [P + 1] * 4,
    "several_blocks": [CAP, 129, 300 % CAP, 2 * P + 3],
    "idle_rows_on_the_trash_page": [40, None, 9, None],
}
WHICH_LAYER = {"traced_layer": (LAYERS - 1, False), "static_layer": (1, True)}


@pytest.mark.parametrize("layer", list(WHICH_LAYER))
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("scale", SCALES)
def test_kernel_matches_attend_absorbed(blocks, scale, dtype, case, layer):
    cfg, bp = blocks[scale, dtype]
    layer, static = WHICH_LAYER[layer]
    pages, q_n, q_r, tables, lengths = _problem(cfg, CASES[case], layer)
    got = _through_kernel(bp, cfg, pages, q_n, q_r, tables, lengths, layer, static)
    want = _gathered(bp, cfg, pages, q_n, q_r, tables, lengths, layer)
    assert got.shape == want.shape and got.dtype == cfg.dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype],
    )


def test_scales_differ_from_one_over_root_width(blocks):
    """The two scales the cases run: the block's own, neither the GQA
    kernel's ``1 / sqrt(width)`` nor each other."""
    widths = {mla.row_width(blocks[s, "float32"][0]) for s in SCALES}
    scales = {mla.softmax_scale(blocks[s, "float32"][0]) for s in SCALES}
    assert len(scales) == 2 and not scales & {w ** -0.5 for w in widths}


def test_kernel_follows_the_table_not_the_pool(blocks):
    """A scattered table and the same rows laid out in order give the same
    bits: the walk reads the pages the table names."""
    cfg, bp = blocks["yarn", "bfloat16"]
    pages, q_n, q_r, tables, lengths = _problem(cfg, [CAP, 33, 1, 70], 0)
    flat = np.asarray(tables).reshape(-1)
    order = np.concatenate([[0], flat[flat > 0]])
    in_order = jnp.zeros_like(pages).at[:, :, : len(order)].set(pages[:, :, order])
    renumbered = np.zeros_like(flat)
    renumbered[flat > 0] = np.arange(1, len(order))
    a = _through_kernel(bp, cfg, pages, q_n, q_r, tables, lengths, 0, False)
    b = _through_kernel(
        bp, cfg, in_order, q_n, q_r, jnp.asarray(renumbered.reshape(tables.shape)),
        lengths, 0, False,
    )
    np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------


def _published():
    return transformer_config(_file(), 3072, jnp.bfloat16)


def test_gate_takes_the_serving_shape():
    """A.X-K1 at the cell's widths: pages of 16, rows of 640 (576 held),
    64 heads, 64 slots; the GQA form refuses the block."""
    cfg = _published()
    assert mla.row_width(cfg) == 640 and cfg.n_heads == 64
    assert kv_pager.latent_kernel_fits(cfg, 16, 64, 1, jnp.bfloat16)
    assert not kv_pager.paged_kernel_fits(cfg, 16, 64, 1, jnp.bfloat16)
    assert pa.latent_vmem_bytes(64, 64, 640, 512, 16, jnp.bfloat16) <= pa.VMEM_BUDGET_BYTES


def test_gate_refuses_what_the_kernel_cannot_read(monkeypatch):
    cfg = _published()
    assert not kv_pager.latent_kernel_fits(cfg, 16, 64, 2, jnp.bfloat16)  # a chunk
    assert not kv_pager.latent_kernel_fits(cfg, 16, 1, 256, jnp.bfloat16)  # a prefill
    assert not kv_pager.latent_kernel_fits(cfg, 16, 64, 1, jnp.float32)  # another dtype
    assert not kv_pager.latent_kernel_fits(cfg, 8, 64, 1, jnp.bfloat16)  # half a sublane tile
    assert not kv_pager.latent_kernel_fits(cfg, 16, 4096, 1, jnp.bfloat16)  # past VMEM
    # values (the latent) of whole lane tiles, and not
    for rank, fits in ((384, True), (500, False)):
        other = transformer_config(
            bench_run.overlay(_file(), {"kv_lora_rank": rank}), 3072, jnp.bfloat16
        )
        assert kv_pager.latent_kernel_fits(other, 16, 8, 1, jnp.bfloat16) is fits
    # a row stored ragged, 576 wide
    monkeypatch.setattr(mla, "row_width", mla.page_width)
    assert not kv_pager.latent_kernel_fits(cfg, 16, 64, 1, jnp.bfloat16)


def test_gate_refuses_a_mesh_axis_left_to_partition():
    cfg = _published()
    mesh = jax.make_mesh((2,), ("tp",), axis_types=(jax.sharding.AxisType.Explicit,))
    assert kv_pager.latent_kernel_fits(cfg, 16, 64, 1, jnp.bfloat16)
    with jax.set_mesh(mesh):
        assert not kv_pager.latent_kernel_fits(cfg, 16, 64, 1, jnp.bfloat16)
    assert kv_pager.latent_kernel_fits(cfg, 16, 64, 1, jnp.bfloat16)


def test_gate_refuses_every_other_block():
    """Only an ``mla`` block has one pool whose values are its keys' first
    part: the tiny file's latent of 16 is refused too (not whole tiles)."""
    from tensorframes_tpu.models import transformer as tfm

    gqa = tfm.TransformerConfig(d_model=512, n_heads=4, n_kv_heads=1, dtype=jnp.bfloat16,
                                param_dtype=jnp.bfloat16)
    assert not kv_pager.latent_kernel_fits(gqa, 16, 8, 1, jnp.bfloat16)
    m = _file()
    tiny = transformer_config(bench_run.overlay(m, m["tiny"]), 32, jnp.float32)
    assert not kv_pager.latent_kernel_fits(tiny, 8, 3, 1, jnp.float32)
