"""The params the serving executables are handed (PR 42): the q, k and v
projections turned once, ``[L, out, in]`` (``kv_pager.serving_params``), so
that a layer's slice is read by the product where it lies instead of being
copied to that layout every layer of every step.

Everything here runs at tiny widths on the CPU.  The turned params give the
plain params' q, k and v to the rounding of the compute type, and the
scheduler's replies are the replies it gave with the plain params: the turn
changes where a weight lies, not what a step computes.  What the chip's
compiler does with either layout is ``tests/test_paged_compile.py``'s.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as bench_run  # noqa: E402
from perfbench.drivers import bridge_decode_axk1, bridge_decode_brumby  # noqa: E402
from perfbench.refs import axk1_decoder, brumby_decoder  # noqa: E402
from tensorframes_tpu import observability as obs  # noqa: E402
from tensorframes_tpu.bridge.coalescer import DecodeScheduler  # noqa: E402
from tensorframes_tpu.models import decode, kv_pager, quant, retention  # noqa: E402
from tensorframes_tpu.models import transformer as tfm  # noqa: E402

CAP = 32


def _gqa(dtype):
    cfg = tfm.TransformerConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq=CAP, dtype=dtype, param_dtype=dtype,
    )
    return cfg, tfm.init(jax.random.PRNGKey(0), cfg)


def _tiny(name, driver, ref, dtype):
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        m = json.load(f)
    m = bench_run.overlay(m, m["tiny"])
    return driver.transformer_config(m, CAP, dtype), ref.make_weights(7, m, dtype)


MODELS = {
    "gqa": _gqa,
    "retention": lambda dt: _tiny(
        "brumby_14b_l8", bridge_decode_brumby, brumby_decoder, dt
    ),
    "mla": lambda dt: _tiny("axk1_l7_ep16", bridge_decode_axk1, axk1_decoder, dt),
}


def _layer(blocks, i):
    return jax.tree.map(lambda a: a[i], blocks)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", ["gqa", "retention"])
def test_turned_projections_give_the_plain_q_k_and_v(kind, dtype):
    cfg, params = MODELS[kind](dtype)
    turned = kv_pager.serving_params(params, cfg)
    for k in ("wq", "wk", "wv"):
        w = turned["blocks"][k]
        assert isinstance(w, tfm.OutIn)
        np.testing.assert_array_equal(
            np.asarray(w.w), np.swapaxes(np.asarray(params["blocks"][k]), -1, -2)
        )
        # the accessor turns it back: any other consumer reads [in, out]
        np.testing.assert_array_equal(
            np.asarray(tfm.weight(w, dtype)), np.asarray(params["blocks"][k])
        )
    # everything else is the very same array
    for k, v in params["blocks"].items():
        if k not in ("wq", "wk", "wv"):
            assert turned["blocks"][k] is v
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(3, 5, cfg.d_model)), dtype
    )
    positions = jnp.broadcast_to(jnp.arange(5, dtype=jnp.int32), (3, 5))
    qkv = tfm._attn_qkv if kind == "gqa" else retention.project
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    for i in range(cfg.n_layers):
        want = qkv(_layer(params["blocks"], i), x, positions, cfg)
        got = qkv(_layer(turned["blocks"], i), x, positions, cfg)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=tol, atol=tol,
            )


def test_blocks_that_project_otherwise_and_int8_are_left_as_they_are():
    cfg, params = MODELS["mla"](jnp.float32)
    assert kv_pager.serving_params(params, cfg) is params
    assert not kv_pager.projects_in_place(params)
    cfg, params = _gqa(jnp.float32)
    q = quant.quantize_params(params)
    turned = kv_pager.serving_params(q, cfg)
    assert all(
        isinstance(turned["blocks"][k], tfm.QTensor) for k in ("wq", "wk", "wv")
    )
    assert not kv_pager.projects_in_place(turned)
    assert kv_pager.projects_in_place(kv_pager.serving_params(params, cfg))


def test_a_tp_sharded_projection_keeps_its_spec_turned():
    devices = np.array(jax.devices()[:2])
    if devices.size < 2:
        pytest.skip("needs two devices")
    cfg, params = _gqa(jnp.float32)
    mesh = Mesh(devices, ("tp",))
    sharded = dict(params)
    sharded["blocks"] = {
        k: jax.device_put(v, NamedSharding(mesh, P(*tfm.block_spec(k))))
        if k in ("wq", "wk", "wv", "wo") else v
        for k, v in params["blocks"].items()
    }
    turned = kv_pager.serving_params(sharded, cfg)
    for k in ("wq", "wk", "wv"):
        w = turned["blocks"][k].w
        assert w.sharding.spec == P(None, "tp", None)
        np.testing.assert_array_equal(
            np.asarray(w), np.swapaxes(np.asarray(params["blocks"][k]), -1, -2)
        )
    assert turned["blocks"]["wo"] is sharded["blocks"]["wo"]


def _serve(cfg, params, prompts):
    sched = DecodeScheduler(params, cfg, max_slots=2, tokens_per_page=4, max_seq=CAP)
    try:
        c0 = obs.counters()
        outs = [sched.submit(p, 4, timeout_s=120) for p in prompts]
        return outs, obs.counters_delta(c0), sched
    finally:
        sched.close()


@pytest.mark.parametrize("kind", list(MODELS))
def test_scheduler_counts_the_steps_that_read_in_place_and_replies_as_before(
    kind, monkeypatch
):
    """``decode_proj_in_place_steps`` is every step of a GQA and a retention
    model and none of a latent one's; the replies are those the scheduler
    gives when it is handed the params as they are."""
    cfg, params = MODELS[kind](jnp.float32)
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=n).astype(np.int32) for n in (5, 9)
    ]
    outs, d, sched = _serve(cfg, params, prompts)
    assert d["decode_steps"] > 0
    turned = kind != "mla"
    assert d["decode_proj_in_place_steps"] == (d["decode_steps"] if turned else 0)
    assert sched._step_counts["decode_proj_in_place_steps"] == int(turned)
    assert "tfs_decode_proj_in_place_steps_total" in obs.metrics_text()
    monkeypatch.setattr(kv_pager, "serving_params", lambda p, c: p)
    plain, d_plain, _ = _serve(cfg, params, prompts)
    assert d_plain["decode_proj_in_place_steps"] == 0
    assert outs == plain
    if kind == "gqa":
        # and the contiguous path, which keeps [in, out], gives them too
        want = [
            [int(t) for t in np.asarray(decode.generate(
                params, jnp.asarray(p[None]), cfg, 4, cache_len=sched.cap
            ))[0, len(p):]]
            for p in prompts
        ]
        assert outs == want
