"""Pallas flash-attention kernel vs the XLA reference attention.

Golden-value testing in interpret mode on the CPU mesh (the same kernel
code lowers to Mosaic on TPU); reference numerics come from
``parallel/ring.py::full_attention`` — the single home of the attention
numerics policy."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorframes_tpu.parallel.flash import flash_attention
from tensorframes_tpu.parallel.ring import full_attention


def _qkv(B, L, H, D, dtype, seed=0, Lk=None):
    rng = np.random.RandomState(seed)
    Lk = Lk or L
    return (
        jnp.asarray(rng.randn(B, L, H, D), dtype),
        jnp.asarray(rng.randn(B, Lk, H, D), dtype),
        jnp.asarray(rng.randn(B, Lk, H, D), dtype),
    )


@pytest.mark.parametrize(
    "shape",
    [
        (2, 16, 2, 8),     # tiny
        (1, 128, 4, 16),   # exactly one q/k block
        (1, 130, 4, 16),   # padded tail block
        (2, 257, 2, 8),    # multiple blocks + tail
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_matches_reference_f32(shape, causal):
    q, k, v = _qkv(*shape, jnp.float32)
    got = flash_attention(q, k, v, causal)
    ref = full_attention(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_matches_reference_bf16():
    q, k, v = _qkv(1, 64, 2, 8, jnp.bfloat16)
    got = flash_attention(q, k, v, True)
    ref = full_attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref, np.float32),
        rtol=0.05,
        atol=0.05,
    )


def test_interpret_mode_is_never_silent(caplog):
    """Off the TPU the kernels run interpreted; on a host whose TPU failed
    to initialise that would be reference code standing in for the kernel,
    so the default says so — once — and naming the mode says nothing."""
    from tensorframes_tpu import envutil

    q, k, v = _qkv(1, 16, 2, 8, jnp.float32)
    envutil._warned_once.discard("flash.interpret")
    with caplog.at_level("WARNING", logger="tensorframes_tpu.flash"):
        flash_attention(q, k, v, True, 128, 128, True)  # asked for: quiet
        assert caplog.records == []
        flash_attention(q, k, v, True)
        jax.grad(lambda q: flash_attention(q, k, v, True).sum())(q)
    assert len(caplog.records) == 1
    assert "INTERPRET" in caplog.text and "'cpu'" in caplog.text


def test_cross_attention_lengths():
    q, k, v = _qkv(1, 24, 2, 8, jnp.float32, Lk=40)
    got = flash_attention(q, k, v, False)
    ref = full_attention(q, k, v, False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_small_block_sizes_stream_many_blocks():
    q, k, v = _qkv(1, 64, 2, 8, jnp.float32)
    got = flash_attention(q, k, v, True, 16, 16)
    ref = full_attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_gradients_match_reference():
    q, k, v = _qkv(1, 32, 2, 8, jnp.float32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True) ** 2).sum()

    def loss_ref(q, k, v):
        return (full_attention(q, k, v, True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


@pytest.mark.parametrize(
    "shape,causal",
    [
        ((1, 130, 2, 8), True),    # padded tail block
        ((2, 257, 2, 8), False),   # multiple blocks + tail, non-causal
        ((1, 64, 2, 8), True),
    ],
)
def test_gradients_match_reference_padded_and_noncausal(shape, causal):
    """The Pallas backward (lse-recompute kernels) must match the XLA
    reference on padded tails and both mask modes (VERDICT r2 next #5)."""
    q, k, v = _qkv(*shape, jnp.float32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal) ** 2).sum()

    def loss_ref(q, k, v):
        return (full_attention(q, k, v, causal) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_gradients_cross_attention_lengths():
    q, k, v = _qkv(1, 24, 2, 8, jnp.float32, Lk=40)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, False) ** 2).sum()

    def loss_ref(q, k, v):
        return (full_attention(q, k, v, False) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_gradients_bf16():
    q, k, v = _qkv(1, 64, 2, 8, jnp.bfloat16)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True).astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        return (full_attention(q, k, v, True).astype(jnp.float32) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.1, atol=0.1,
        )


def test_backward_has_no_quadratic_intermediate():
    """The O(L) memory claim now covers training: the compiled backward
    must not materialise an [L, L] score tensor (the XLA reference path
    does).  Checked via the optimized HLO (VERDICT r2 weak #3)."""
    L = 1024
    q, k, v = _qkv(1, L, 1, 8, jnp.float32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True) ** 2).sum()

    def loss_ref(q, k, v):
        return (full_attention(q, k, v, True) ** 2).sum()

    flash_hlo = (
        jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
        .lower(q, k, v).compile().as_text()
    )
    ref_hlo = (
        jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))
        .lower(q, k, v).compile().as_text()
    )
    quad = f"{L},{L}"
    assert quad in ref_hlo  # the reference DOES materialise scores
    assert quad not in flash_hlo, "flash backward materialised [L, L]"


def test_transformer_flash_impl_matches_full():
    import dataclasses

    from tensorframes_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=97,
        d_model=32,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,   # GQA: kv-width K/V via the kernel index maps
        d_ff=64,
        max_seq=32,
        dtype=jnp.float32,
    )
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 97)
    full = tfm.apply(params, toks, cfg)
    flash = tfm.apply(
        params, toks, dataclasses.replace(cfg, attn_impl="flash")
    )
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(full), rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------- ring flash local step ----


def _ring_golden(q, k, v, causal, impl, devices):
    from jax.sharding import AxisType, Mesh

    import numpy as _np

    from tensorframes_tpu.parallel.ring import ring_attention

    mesh = Mesh(
        _np.array(devices).reshape(1, 1, 8, 1),
        ("pp", "dp", "sp", "tp"),
        axis_types=(AxisType.Auto,) * 4,
    )
    with jax.set_mesh(mesh):
        return np.asarray(
            jax.jit(
                lambda q, k, v: ring_attention(q, k, v, causal, impl=impl)
            )(q, k, v)
        )


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_ring_xla(devices, causal):
    """The Pallas local step composed into the sp=8 ring must reproduce the
    XLA ring (which is itself golden-tested against unsharded attention)."""
    rng = np.random.RandomState(0)
    B, L, H, D = 2, 64, 2, 8  # C = L/sp = 8 per device
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    got = _ring_golden(q, k, v, causal, "flash", devices)
    ref = _ring_golden(q, k, v, causal, "xla", devices)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    # and against the unsharded oracle directly
    oracle = np.asarray(full_attention(q, k, v, causal))
    np.testing.assert_allclose(got, oracle, rtol=2e-4, atol=2e-4)


def test_ring_flash_gradients(devices):
    """Backward (the hand-written ring) over the flash forward: gradients
    must match the XLA-forward ring's."""
    rng = np.random.RandomState(1)
    B, L, H, D = 1, 32, 2, 8
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    from jax.sharding import AxisType, Mesh

    from tensorframes_tpu.parallel.ring import ring_attention

    mesh = Mesh(
        np.array(jax.devices()).reshape(1, 1, 8, 1),
        ("pp", "dp", "sp", "tp"),
        axis_types=(AxisType.Auto,) * 4,
    )

    def loss(impl, q, k, v):
        return (ring_attention(q, k, v, True, impl=impl) ** 2).sum()

    with jax.set_mesh(mesh):
        gf = jax.jit(jax.grad(lambda q: loss("flash", q, k, v)))(q)
        gx = jax.jit(jax.grad(lambda q: loss("xla", q, k, v)))(q)
    np.testing.assert_allclose(
        np.asarray(gf), np.asarray(gx), rtol=2e-4, atol=2e-4
    )


def test_transformer_ring_flash_matches_ring(devices):
    import dataclasses

    from jax.sharding import AxisType, Mesh

    from tensorframes_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=67,
        d_model=16,
        n_layers=2,
        n_heads=2,
        n_kv_heads=2,
        d_ff=32,
        max_seq=32,
        dtype=jnp.float32,
        attn_impl="ring",
    )
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 67)
    mesh = Mesh(
        np.array(jax.devices()).reshape(1, 1, 8, 1),
        ("pp", "dp", "sp", "tp"),
        axis_types=(AxisType.Auto,) * 4,
    )
    with jax.set_mesh(mesh):
        ref = jax.jit(lambda p, t: tfm.apply(p, t, cfg))(params, toks)
        cfg_f = dataclasses.replace(cfg, attn_impl="ring_flash")
        got = jax.jit(lambda p, t: tfm.apply(p, t, cfg_f))(params, toks)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_flash_rejects_custom_positions():
    import dataclasses

    from tensorframes_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=17, d_model=8, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=16, max_seq=8, dtype=jnp.float32, attn_impl="flash",
    )
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 17)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32) + 4, (1, 8))
    with pytest.raises(ValueError, match="row-major"):
        tfm.apply(params, toks, cfg, positions=pos)
    # default positions stay fine
    assert tfm.apply(params, toks, cfg).shape == (1, 8, 17)


@pytest.mark.parametrize("impl", ["flash", "ring_flash"])
def test_kernels_lower_for_tpu_under_a_mesh(devices, impl, monkeypatch):
    """Mosaic kernels cannot be partitioned automatically: where a
    pallas_call lowers for the TPU, the region must be manual over EVERY
    mesh axis, or jax raises — which interpret mode on XLA:CPU never does,
    so the four-chip run was the first to see it.  Lowering for the
    ``tpu`` platform applies the same rule without a chip: under a
    dp x sp x tp mesh every kernel (forward, backward, ring step) must
    lower, and the interpreted run must still match the unsharded result
    on GSPMD-sharded GQA inputs."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorframes_tpu.parallel import flash
    from tensorframes_tpu.parallel.mesh import training_mesh
    from tensorframes_tpu.parallel.ring import ring_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(4, 256, 4, 64), jnp.float32)
    k = jnp.asarray(rng.randn(4, 256, 2, 64), jnp.float32)
    v = jnp.asarray(rng.randn(4, 256, 2, 64), jnp.float32)

    def attend(q, k, v):
        if impl == "flash":
            return flash_attention(q, k, v, True)
        return ring_attention(q, k, v, True, impl="flash")

    def grad():  # a fresh function each time: jit caches traces by function
        return jax.grad(
            lambda q, k, v: (attend(q, k, v) ** 2).sum(), argnums=(0, 1, 2)
        )

    want = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    mesh = training_mesh(dp=2, sp=2, tp=2)
    with jax.set_mesh(mesh):
        sh = NamedSharding(mesh, P("dp", "sp", "tp", None))
        qs, ks, vs = (jax.device_put(a, sh) for a in (q, k, v))
        got = jax.jit(grad())(qs, ks, vs)
        monkeypatch.setattr(flash, "_resolve_interpret", lambda _: False)
        with jax.enable_x64(False):  # as on the chip
            lowered = jax.jit(grad()).trace(qs, ks, vs).lower(
                lowering_platforms=("tpu",)
            )
    assert "tpu_custom_call" in lowered.as_text()
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5
        )


def test_ring_step_rejects_unaligned_chunk():
    """ADVICE r2: a chunk length with no multiple-of-8 block must fail
    loudly (Mosaic tiling would reject it on real TPU; interpret mode
    would silently accept)."""
    from tensorframes_tpu.parallel.flash import _chunk_block

    assert _chunk_block(128) == 128
    assert _chunk_block(24) == 8
    with pytest.raises(ValueError, match="divisible by 8"):
        _chunk_block(7)


def test_attn_impl_auto_dispatch():
    """'auto' picks flash at/above flash_min_len (row-major positions) and
    the fused XLA path below it or with custom positions."""
    import dataclasses

    from tensorframes_tpu.models import transformer as tfm

    cfg = dataclasses.replace(
        tfm.TransformerConfig(
            vocab_size=32, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq=64, dtype=jnp.float32,
        ),
        attn_impl="auto",
        flash_min_len=32,
    )
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 32)

    # L=32 >= flash_min_len -> flash; parity with the explicit impls
    auto = tfm.apply(params, toks, cfg)
    flash = tfm.apply(
        params, toks, dataclasses.replace(cfg, attn_impl="flash")
    )
    full = tfm.apply(params, toks, dataclasses.replace(cfg, attn_impl="full"))
    np.testing.assert_allclose(
        np.asarray(auto), np.asarray(flash), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(auto), np.asarray(full), rtol=1e-4, atol=1e-4
    )

    # short L -> full path exactly
    short = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 32)
    auto_s = tfm.apply(params, short, cfg)
    full_s = tfm.apply(
        params, short, dataclasses.replace(cfg, attn_impl="full")
    )
    np.testing.assert_array_equal(np.asarray(auto_s), np.asarray(full_s))

    # custom positions do NOT raise under auto (fall back to full)
    pos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (2, 32)) + 1
    out = tfm.apply(params, toks, cfg, positions=pos)
    ref = tfm.apply(
        params, toks, dataclasses.replace(cfg, attn_impl="full"),
        positions=pos,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_attn_impl_auto_picks_ring_under_sp_mesh(devices):
    """Under an sp>1 mesh, 'auto' resolves to the ring family (the
    sequence arrives sharded); parity with explicit ring."""
    import dataclasses

    from jax.sharding import AxisType, Mesh

    from tensorframes_tpu.models import transformer as tfm

    cfg = dataclasses.replace(
        tfm.TransformerConfig(
            vocab_size=32, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq=64, dtype=jnp.float32,
        ),
        attn_impl="auto",
        flash_min_len=64,  # L=64 -> ring_flash (chunk 8 tiles)
    )
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 32)
    ref = tfm.apply(params, toks, dataclasses.replace(cfg, attn_impl="full"))
    mesh = Mesh(
        np.array(devices).reshape(1, 1, 8, 1),
        ("pp", "dp", "sp", "tp"),
        axis_types=(AxisType.Auto,) * 4,
    )
    with jax.set_mesh(mesh):
        auto = jax.jit(lambda p, t: tfm.apply(p, t, cfg))(params, toks)
        ring = jax.jit(
            lambda p, t: tfm.apply(
                p, t, dataclasses.replace(cfg, attn_impl="ring_flash")
            )
        )(params, toks)
    np.testing.assert_allclose(
        np.asarray(auto), np.asarray(ring), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(auto), np.asarray(ref), atol=5e-4
    )


def test_attn_impl_auto_indivisible_seq_falls_back_to_full(devices):
    """L not divisible by sp cannot ring-shard: auto must pick the GSPMD
    full path instead of crashing in shard_map (review r3)."""
    import dataclasses

    from jax.sharding import AxisType, Mesh

    from tensorframes_tpu.models import transformer as tfm

    cfg = dataclasses.replace(
        tfm.TransformerConfig(
            vocab_size=32, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq=64, dtype=jnp.float32,
        ),
        attn_impl="auto",
    )
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 60), 0, 32)  # 60%8!=0
    ref = tfm.apply(params, toks, dataclasses.replace(cfg, attn_impl="full"))
    mesh = Mesh(
        np.array(devices).reshape(1, 1, 8, 1),
        ("pp", "dp", "sp", "tp"),
        axis_types=(AxisType.Auto,) * 4,
    )
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, t: tfm.apply(p, t, cfg))(params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-4)


def test_pipeline_with_ring_flash(devices):
    """pp>1 + sp>1 + ring_flash: the sp axis must join the pp-manual
    region (the 'ring'-only guard missed ring_flash — review r3)."""
    import dataclasses

    from jax.sharding import AxisType, Mesh

    from tensorframes_tpu import train
    from tensorframes_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=32, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq=16, dtype=jnp.float32, attn_impl="ring_flash",
    )
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 32)
    tgts = jnp.roll(toks, -1, axis=1)
    ref = float(tfm.loss_fn(
        params, toks, tgts, dataclasses.replace(cfg, attn_impl="full")
    ))
    mesh = Mesh(
        np.array(devices).reshape(2, 2, 2, 1),
        ("pp", "dp", "sp", "tp"),
        axis_types=(AxisType.Auto,) * 4,
    )
    tcfg = train.TrainConfig(pp_stages=2, microbatches=2)
    with jax.set_mesh(mesh):
        loss = float(jax.jit(
            lambda p: train.loss_pipelined(p, toks, tgts, cfg, tcfg)
        )(params))
    assert abs(loss - ref) < 5e-3, (loss, ref)


# -- GQA: kv-width K/V through the kernel index maps ------------------------


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gqa_forward_matches_repeated(causal):
    B, L, H, KVH, Dh = 2, 64, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, L, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, KVH, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, KVH, Dh), jnp.float32)
    out = flash_attention(q, k, v, causal, 32, 32)
    ref = full_attention(
        q,
        jnp.repeat(k, H // KVH, 2),
        jnp.repeat(v, H // KVH, 2),
        causal,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gqa_grads_match_repeated_oracle():
    """dK/dV come out kv-width, equal to the repeated formulation's grads
    group-summed (the repeat's VJP) — accumulated inside the backward
    kernel over the group's query heads."""
    B, L, H, KVH, Dh = 1, 48, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (B, L, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, KVH, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, KVH, Dh), jnp.float32)

    def loss(a, b, c):
        return jnp.sum(flash_attention(a, b, c, True, 16, 16) ** 2)

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert gk.shape == (B, L, KVH, Dh)

    def oracle(a, b, c):
        return jnp.sum(
            full_attention(
                a, jnp.repeat(b, H // KVH, 2), jnp.repeat(c, H // KVH, 2), True
            )
            ** 2
        )

    rq, rk, rv = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(rq), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(rk), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), atol=1e-4)


def test_flash_gqa_rejects_indivisible_heads():
    q = jnp.zeros((1, 16, 8, 8), jnp.float32)
    k = jnp.zeros((1, 16, 3, 8), jnp.float32)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, k, True)


# ---------------------------------------------------------------------------
# the serving prefill's forward, causal with or without a sliding window
# ---------------------------------------------------------------------------


def _windowed_reference(q, k, v, window):
    """softmax over the keys in (t - window, t] of each query t (0: all
    keys up to t), float32, GQA by repetition."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    L = q.shape[1]
    t = jnp.arange(L)
    mask = t[:, None] >= t[None, :]
    if window:
        mask &= t[:, None] - t[None, :] < window
    s = jnp.einsum("blhd,bshd->bhls", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhls,bshd->blhd", p, v)


@pytest.mark.parametrize("window", [0, 1, 7, 40, 64, 150])
@pytest.mark.parametrize("L", [64, 100])
def test_prefill_window_matches_masked_reference(window, L):
    """Blocks of 16 keys: windows of a key, shorter than a block, over
    several, and wider than the chunk; a length that pads the last block."""
    from tensorframes_tpu.parallel.flash import flash_prefill

    q, k, v = _qkv(1, L, 4, 16, jnp.float32, seed=window)
    k, v = k[:, :, :2], v[:, :, :2]
    got = flash_prefill(q, k, v, window, block=16)
    np.testing.assert_allclose(got, _windowed_reference(q, k, v, window), atol=1e-5)


def test_prefill_without_a_window_is_the_causal_forward_bit_for_bit():
    """No window is ``flash_attention``'s causal forward, and so is a window
    at least as long as the chunk (the grid then walks every causal block in
    the same order)."""
    from tensorframes_tpu.parallel.flash import flash_prefill

    q, k, v = _qkv(1, 96, 4, 16, jnp.float32, seed=3)
    k, v = k[:, :, :2], v[:, :, :2]
    causal = flash_attention(q, k, v, True, 32, 32)
    np.testing.assert_array_equal(flash_prefill(q, k, v, 0, block=32), causal)
    np.testing.assert_array_equal(flash_prefill(q, k, v, 96, block=32), causal)
    with pytest.raises(ValueError):
        flash_prefill(q, k, v, -1)
