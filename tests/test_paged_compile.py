"""The decode step's Pallas kernel and both serving executables, compiled at
the benchmark's real widths for a TPU v5e that is described and not
attached (PR 30; PR 33: the pools stay where they lie; PR 42: the q, k and
v projections too).

Interpret mode (``tests/test_paged_attention.py``) checks the kernel's
arithmetic; it cannot see a slice that is not aligned to the tiling, a
DMA the hardware cannot describe, or more VMEM than a kernel may use.
The TPU's compiler is installed here and refuses those for a chip it is
only told about.  Nothing runs: no time or result comes from this file.

The topology is described inside a fixture, after a test of this file has
started, and never at import (one process at a time may load the TPU's
library; every xdist worker imports every test file).  Keep such tests in
this one file.
"""

import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.drivers import (  # noqa: E402
    bridge_decode_axk1, bridge_decode_brumby, bridge_decode_falcon_h1,
)
from perfbench.drivers.bridge_decode_zaya import transformer_config  # noqa: E402
from perfbench.refs import (  # noqa: E402
    axk1_decoder, brumby_decoder, falcon_h1_decoder, inception_v3,
    transformer_decoder, zaya_decoder,
)
from tensorframes_tpu.models import (  # noqa: E402
    cca, inception, kv_pager, mla, retention, ssm,
)
from tensorframes_tpu.models import transformer as tfm  # noqa: E402
from tensorframes_tpu.parallel import paged_attention as pa  # noqa: E402
from tensorframes_tpu.parallel import retention as rk  # noqa: E402
from tensorframes_tpu.parallel import ssm as sk  # noqa: E402

# (configuration, the traffic file whose slots and capacity serve it)
CELLS = {
    "zaya1_8b_l20": "decode_reason",
    "mistral_7b_l8": "decode_chat",
}
# the latent block's cell: its step takes the kernel's latent form
LATENT = {"axk1_l7_ep16": "decode_grounded"}


def _load(kind, name):
    with open(os.path.join(ROOT, "perfbench", kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_not_interpreted(monkeypatch):
    """The persistent cache off (a compile for a described chip can be
    written to it and never read back), and the kernel as the chip gets
    it: ``jax.default_backend()`` is the CPU here, so the test steers
    what the program would decide from it, and the suite's x64 (on for
    dtype fidelity on the CPU) is off as it is on the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(pa, "_resolve_interpret", lambda interpret: False)
    monkeypatch.setattr(rk, "_resolve_interpret", lambda interpret: False)
    monkeypatch.setattr(sk, "_resolve_interpret", lambda interpret: False)
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _cell(config, sharding):
    """``(cfg, args, kwargs)`` of the scheduler's ``paged_decode_step`` for
    a benchmark configuration, as shapes on the described chip (the params
    as the scheduler hands them: ``kv_pager.serving_params``)."""
    traffic = {**CELLS, **LATENT}[config]
    m, serve = _load("configs", config), _load("traffic", traffic)["serve"]
    dtype = jnp.dtype(m["dtype"])
    if m["reference"] == "zaya_decoder":
        cfg = transformer_config(m, serve["max_seq"], dtype)
        make = zaya_decoder.make_weights
    elif m["reference"] == "axk1_decoder":
        cfg = bridge_decode_axk1.transformer_config(m, serve["max_seq"], dtype)
        make = axk1_decoder.make_weights
    else:
        cfg = tfm.TransformerConfig(
            vocab_size=m["vocab_size"], d_model=m["hidden_size"],
            n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
            max_seq=serve["max_seq"], rope_theta=float(m["rope_theta"]),
            dtype=dtype, param_dtype=dtype,
        )
        make = transformer_decoder.make_weights
    slots, P = serve["max_slots"], serve["tokens_per_page"]
    max_pages = kv_pager.pages_for(serve["max_seq"], P)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            tree,
        )

    pool = jax.eval_shape(
        lambda: kv_pager.PagePool(
            cfg, slots * max_pages + 1, tokens_per_page=P, slots=slots
        ).k_pages
    )
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    args = on_chip((
        jax.eval_shape(lambda: kv_pager.serving_params(make(0, m, dtype), cfg)),
        i32(slots), i32(slots, max_pages), i32(slots), pool,
        None if cfg.block.attention == "mla" else pool,  # the one pool
    ))
    kwargs = {}
    if cfg.block.attention == "cca":
        kwargs["state"] = on_chip(
            jax.eval_shape(lambda: cca.init_state(cfg, slots, dtype))
        )
    return cfg, args, kwargs


@pytest.mark.parametrize("config", list(CELLS))
def test_kernel_compiles_at_real_widths(config, one_chip, compiled_not_interpreted):
    cfg, (_, toks, tables, indices, kp, _), _ = _cell(config, one_chip)
    assert kv_pager.paged_kernel_fits(
        cfg, kp.shape[3], toks.shape[0], 1, kp.dtype
    )
    shape = lambda *s, dt=cfg.dtype: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip
    )
    compiled = jax.jit(pa.paged_attention).lower(
        shape(toks.shape[0], cfg.n_heads, cfg.head_dim),
        shape(*kp.shape), shape(*kp.shape), tables, indices,
        shape(dt=jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and pa.KERNEL_NAME in text
    # q and the output whole in VMEM, the stacks read in HBM at the layer
    # asked for: nothing else is asked of the device
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("config", list(CELLS))
def test_decode_step_compiles_with_the_kernel_and_no_gather(
    config, one_chip, compiled_not_interpreted
):
    """The scheduler's whole step at the cell's slots and capacity: the
    kernel is in it, and nothing of the capacity's extent (slots x
    max_pages pages, gathered) is."""
    cfg, args, kwargs = _cell(config, one_chip)
    compiled = kv_pager.paged_decode_step.lower(*args, cfg, **kwargs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and pa.KERNEL_NAME in text
    slots, max_pages = args[2].shape
    P, dh = args[4].shape[3:]
    assert f"bf16[{slots * max_pages},{P}," not in text
    assert f"[{slots},{max_pages * P},{cfg.n_kv_heads},{dh}]" not in text
    _pool_keeps_its_layout(text, args[4].shape)
    _pools_stay_where_they_lie(compiled, args[4])
    mem = compiled.memory_analysis()
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes < 16 * 2**30
    )


# `%name = bf16[8,8,1537,16,128]{4,3,2,1,0:T(8,128)(2,1)} opcode(...)`,
# in the entry computation, a loop's body or a fusion's
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([0-9,]*)\]\{([0-9,]*)\S* ([\w\-]+)\("
)


def _pool_values(text, pool_shape):
    """``(opcode, layout, line)`` of every instruction whose result holds
    the stack's or one layer's pool, in whatever shape: the stack, a layer
    of it, or either flattened to windows for the page write.  (A pool has
    ``n_pages`` = slots x pages + 1 among its factors, which no weight
    has.)"""
    sizes = {math.prod(pool_shape), math.prod(pool_shape[1:])}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(2):
            dims = [int(d) for d in m.group(2).split(",")]
            if math.prod(dims) in sizes:
                yield m.group(4), m.group(3), line.strip()


def _pool_keeps_its_layout(text, pool_shape):
    """Every value that holds the stacked pools (or a layer's) in the
    compiled program lies row-major, layers then heads outermost: a page
    write windowed over (kvh, Dh) made XLA turn the whole pool head-minor
    (``{3,0,2,1:T(2,128)}``) and back around every write, 18% of a traced
    window (PERF.md §6, PR 30)."""
    layouts = {layout for _, layout, _ in _pool_values(text, pool_shape)}
    row_major = {"4,3,2,1,0", "2,1,0", "1,0"}
    if pool_shape[1] == 1:  # one head: which of the two outer axes leads is no matter
        row_major.add("4,3,2,0,1")
    assert layouts and layouts <= row_major, layouts


def _pools_stay_where_they_lie(compiled, pool, pools=2):
    """The compiled executable moves no pool (PR 33): no ``dynamic-slice``,
    ``dynamic-update-slice`` or ``copy`` gives a layer's pool or the stack
    (as the scan's ``xs`` / ``ys`` every layer of every step sliced its
    pool out and wrote it back, a sixth of both decode windows), the only
    fusions that give one are the page write's scatters, and both stacks
    are aliased to the arguments that were donated, with no buffer of a
    pool's size among the temporaries."""
    moved = [
        line[:200] for op, _, line in _pool_values(compiled.as_text(), pool.shape)
        if op in ("copy", "dynamic-slice", "dynamic-update-slice")
        or (op == "fusion" and "page_write/scatter" not in line)
    ]
    assert not moved, moved
    stack = math.prod(pool.shape) * pool.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools * stack
    if pools == 2:  # the latent step's gathered rows are a layer's pool in size: it states its own bound
        assert mem.temp_size_in_bytes < stack // pool.shape[0]


@pytest.mark.parametrize("config", list(CELLS))
def test_prefill_writes_whole_pages_and_keeps_the_layout(
    config, one_chip, compiled_not_interpreted
):
    """The 256 bucket's prefill: the page write scatters pages of a head,
    ``[n_layers * kvh * n_pages, P, Dh]`` windows, into the stack left as
    it lies."""
    cfg, (weights, _, tables, _, kp, vp), kwargs = _cell(config, one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip
    )
    if kwargs:
        kwargs["slot"] = i32(1)
    compiled = kv_pager.paged_prefill.lower(
        weights, i32(1, 256), i32(1, tables.shape[1]), i32(1), kp, vp, cfg,
        **kwargs,
    ).compile()
    text = compiled.as_text()
    _pool_keeps_its_layout(text, kp.shape)
    _pools_stay_where_they_lie(compiled, kp)
    n, kvh, n_pages, P, dh = kp.shape
    assert f"[{n * kvh * n_pages},{P},{dh}]" in text


# ---------------------------------------------------------------------------
# the latent block (A.X-K1): ONE pool, absorbed decode through the kernel's
# latent form, the gather path where the gate refuses
# ---------------------------------------------------------------------------


def _capacity_gathered(text, slots, max_pages, P, width):
    """Whether the compiled text holds the table's pages of every slot
    gathered as rows: the capacity, 252 MB a layer at the cell's shapes."""
    return any(
        shape in text for shape in (
            f"bf16[{slots * max_pages},{P},{width}]",
            f"bf16[{slots},{max_pages},{P},{width}]",
            f"bf16[{slots},{max_pages * P},{width}]",
        )
    )


@pytest.mark.parametrize("config", list(LATENT))
def test_latent_kernel_compiles_at_real_widths(config, one_chip, compiled_not_interpreted):
    """64 slots' queries of 64 heads x 640 and their 512-wide output whole in
    VMEM beside one ring of row blocks (``latent_vmem_bytes``, within the
    budget), the stacked pool read in HBM at the layer asked for."""
    cfg, (_, toks, tables, indices, pool, _), _ = _cell(config, one_chip)
    slots, P, width = toks.shape[0], pool.shape[3], pool.shape[4]
    assert kv_pager.latent_kernel_fits(cfg, P, slots, 1, pool.dtype)
    values = cfg.block.latent.kv_rank
    assert pa.latent_vmem_bytes(
        slots, cfg.n_heads, width, values, P, pool.dtype
    ) <= pa.VMEM_BUDGET_BYTES
    shape = lambda *s, dt=cfg.dtype: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip
    )
    compiled = jax.jit(pa.latent_attention, static_argnums=(5, 6)).lower(
        shape(slots, cfg.n_heads, width), shape(*pool.shape), tables, indices,
        shape(dt=jnp.int32), values, mla.softmax_scale(cfg),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and pa.LATENT_KERNEL_NAME in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("config", list(LATENT))
def test_latent_step_attends_in_the_latent_space_and_moves_no_pool(
    config, one_chip, compiled_not_interpreted
):
    """The scheduler's whole step at the cell's 64 slots x 3,072: the
    kernel's latent form reads each row's pages where they lie
    (``latent_kernel_fits``; ``paged_kernel_fits`` refuses the block), the
    one pool donated, aliased and left as it lies — no page of the capacity
    gathered as rows (``bf16[12288,16,640]``, 252 MB a layer) and nothing of
    the capacity's extent expanded by head (``[slots, capacity, 64, 192]``
    keys would be 4.8 GB a layer)."""
    cfg, args, kwargs = _cell(config, one_chip)
    slots, max_pages = args[2].shape
    pool = args[4]
    P, width = pool.shape[3:]
    assert args[5] is None and pool.shape[1] == 1 and width == mla.row_width(cfg) == 640
    assert not kv_pager.paged_kernel_fits(cfg, P, slots, 1, pool.dtype)
    assert kv_pager.latent_kernel_fits(cfg, P, slots, 1, pool.dtype)
    compiled = kv_pager.paged_decode_step.lower(*args, cfg, **kwargs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and pa.LATENT_KERNEL_NAME in text
    assert pa.KERNEL_NAME not in text
    _pool_keeps_its_layout(text, pool.shape)
    _pools_stay_where_they_lie(compiled, pool, pools=1)
    assert not _capacity_gathered(text, slots, max_pages, P, width)
    cap, lat = max_pages * P, cfg.block.latent
    for per_head in (lat.nope_dim + lat.rope_dim, lat.nope_dim, lat.v_dim,
                     lat.nope_dim + lat.v_dim):
        assert f"[{slots},{cap},{cfg.n_heads},{per_head}]" not in text
        assert f"[{slots},{cfg.n_heads},{cap},{per_head}]" not in text
    mem = compiled.memory_analysis()
    # no scores over the capacity and no gathered rows: a layer's pool is
    # 252 MB, the step's temporaries are under a fifth of it
    assert mem.temp_size_in_bytes < 2**26
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes < 14 * 2**30
    )


@pytest.mark.parametrize("config", list(LATENT))
def test_latent_step_off_the_kernel_gathers_the_capacity(
    config, one_chip, compiled_not_interpreted, monkeypatch
):
    """What the gate refuses runs the gather path as before: the table's
    pages gathered as rows of ``mla.row_width``, scored over the capacity
    (the test steers the gate; the program has no option)."""
    monkeypatch.setattr(kv_pager, "latent_kernel_fits", lambda *a: False)
    jax.clear_caches()
    try:
        cfg, args, kwargs = _cell(config, one_chip)
        slots, max_pages = args[2].shape
        pool = args[4]
        compiled = kv_pager.paged_decode_step.lower(*args, cfg, **kwargs).compile()
    finally:
        jax.clear_caches()
    text = compiled.as_text()
    assert pa.LATENT_KERNEL_NAME not in text and pa.KERNEL_NAME not in text
    assert _capacity_gathered(text, slots, max_pages, *pool.shape[3:])
    _pool_keeps_its_layout(text, pool.shape)
    _pools_stay_where_they_lie(compiled, pool, pools=1)
    # scores and weights of 64 heads over the capacity, and the gathered rows
    assert 2**26 < compiled.memory_analysis().temp_size_in_bytes < 2**29


@pytest.mark.parametrize("config", list(LATENT))
def test_latent_prefill_writes_whole_pages_of_the_one_pool(
    config, one_chip, compiled_not_interpreted
):
    cfg, (weights, _, tables, _, pool, _), _ = _cell(config, one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip
    )
    compiled = kv_pager.paged_prefill.lower(
        weights, i32(1, 256), i32(1, tables.shape[1]), i32(1), pool, None, cfg
    ).compile()
    text = compiled.as_text()
    _pool_keeps_its_layout(text, pool.shape)
    _pools_stay_where_they_lie(compiled, pool, pools=1)
    n, _, n_pages, P, width = pool.shape
    assert f"[{n * n_pages},{P},{width}]" in text


@pytest.mark.parametrize("config", list(LATENT))
def test_a_ragged_latent_row_would_turn_the_pool(
    config, one_chip, compiled_not_interpreted, monkeypatch
):
    """Why a page stores 640 values for a row of 576 (``mla.row_width``):
    given the ragged minor dimension the chip's compiler lays the pool out
    pages-minor and copies the whole of it every step.  If this stops
    failing the padding can go."""
    monkeypatch.setattr(mla, "row_width", mla.page_width)
    cfg, args, kwargs = _cell(config, one_chip)
    assert args[4].shape[-1] == 576
    compiled = kv_pager.paged_decode_step.lower(*args, cfg, **kwargs).compile()
    with pytest.raises(AssertionError):
        _pool_keeps_its_layout(compiled.as_text(), args[4].shape)


# ---------------------------------------------------------------------------
# the scoring executable (Inception-v3): activations cross HBM in bf16
# ---------------------------------------------------------------------------

# an ENTRY instruction's result type(s), up to its opcode: one array, or
# the tuple of a multi-output fusion
_RESULT = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\(.*?\)|\S+) [\w\-]+\(")
_F32_ARRAY = re.compile(r"\bf32\[([0-9,]+)\]")


def _entry_f32_results(text):
    """Bytes of every float32 array an instruction of the ENTRY computation
    gives: what one executable's fusions write to HBM at four bytes."""
    entry = text[text.index("\nENTRY "):]
    for line in entry[: entry.index("\n}")].splitlines():
        m = _RESULT.match(line)
        for dims in _F32_ARRAY.findall(m.group(1)) if m else ():
            yield 4 * math.prod(int(d) for d in dims.split(","))


_REDUCE_WINDOW = re.compile(
    r"= \w+\[([0-9,]+)\]\S* reduce-window\(.*?to_apply=%?([\w.\-]+)"
)


def _window_sum_channels(text):
    """The minor (channel) dimension of every ``reduce-window`` whose
    reduction adds: the average pools, wherever a fusion took them."""
    bodies, name = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            bodies[name] = ""
        elif name is not None:
            bodies[name] += line + "\n"
    for m in _REDUCE_WINDOW.finditer(text):
        if " add(" in bodies[m.group(2)]:
            yield int(m.group(1).split(",")[-1])


def test_scoring_executable_stores_no_float32_activation(
    one_chip, compiled_not_interpreted
):
    """``jit__run`` of the scoring cells, a 1,024-row block with the weights
    as arguments (as ``perfbench/drivers/frame_score.py`` builds it).  Traced
    in float32 by one strongly typed scalar, its fusions wrote 32.7 GB of
    float32 activations a block (``f32[1024,147,147,64]`` alone 5.66 GB)
    and held 7.09 GB of temporaries, with bf16 operands on the MXU all the
    same; in the type it is given they write 0.01 GB and hold 4.26
    (PERF.md §6, PR 36)."""
    m, traffic = _load("configs", "inception_v3"), _load("traffic", "score_cached")
    dtype, kwargs = jnp.dtype(m["dtype"]), m["program"]["kwargs"]
    rows = traffic["rows_per_chip"] // traffic["blocks_per_chip"]
    assert rows == 1024 and dtype == jnp.bfloat16

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    weights = jax.tree.map(
        on_chip, jax.eval_shape(lambda: inception_v3.make_weights(0, dtype))
    )
    image = on_chip(jax.ShapeDtypeStruct(
        (rows, math.prod(m["input"]["row_shape"])), jnp.dtype(m["input"]["dtype"])
    ))
    compiled = jax.jit(
        lambda w, x: inception.scoring_program(w, dtype=dtype, **kwargs)(x)
    ).lower(weights, image).compile()
    text = compiled.as_text()
    large = [b for b in _entry_f32_results(text) if b >= 100e6]
    assert sum(large) < 1e9, (len(large), sum(large))
    assert compiled.memory_analysis().temp_size_in_bytes < 5e9
    # the nine average pools reduce their branch's 1x1 convolution (32, 64
    # or 192 channels), not the block input pooling first would read
    # (192-2,048 channels: 8.2 GB a block moved where 1.8 do)
    pooled = sorted(_window_sum_channels(text))
    assert pooled == [32, 64, 64] + [192] * 6, pooled


# ---------------------------------------------------------------------------
# the retention block (Brumby): no pages, a float32 state a slot, a kernel
# of its own for the step
# ---------------------------------------------------------------------------


def _retention_cell(sharding):
    """``(cfg, weights, state, slots)`` of ``brumby_14b_l8.decode_documents``
    as shapes on the described chip."""
    m = _load("configs", "brumby_14b_l8")
    serve = _load("traffic", "decode_documents")["serve"]
    dtype = jnp.dtype(m["dtype"])
    cfg = bridge_decode_brumby.transformer_config(m, serve["max_seq"], dtype)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            tree,
        )

    weights = on_chip(jax.eval_shape(
        lambda: kv_pager.serving_params(brumby_decoder.make_weights(0, m, dtype), cfg)
    ))
    state = on_chip(
        jax.eval_shape(lambda: retention.init_state(cfg, serve["max_slots"]))
    )
    return cfg, weights, state, serve["max_slots"]


def _state_values(text, state):
    """``(opcode, line)`` of every instruction whose result is as large as
    the stacked state, one layer of it, or one slot's of a layer."""
    S = state[0].shape
    sizes = {math.prod(S), math.prod(S[1:]), math.prod(S[2:])}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(2):
            dims = [int(d) for d in m.group(2).split(",")]
            if math.prod(dims) in sizes:
                yield m.group(4), line.strip()


def test_retention_kernel_compiles_at_real_widths(one_chip, compiled_not_interpreted):
    cfg, _, (S, z), slots = _retention_cell(one_chip)
    assert kv_pager.retention_kernel_fits(cfg) and S.dtype == jnp.float32
    assert S.shape == (8, 20, 8, 65, 128, 128) and z.shape == (8, 20, 8, 65, 128)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(rk.retention_step, donate_argnums=(4, 5)).lower(
        f32(slots, cfg.n_heads, 128), f32(slots, cfg.n_kv_heads, 128),
        f32(slots, cfg.n_kv_heads, 128), f32(slots, cfg.n_kv_heads), S, z,
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and rk.KERNEL_NAME in text
    mem = compiled.memory_analysis()
    state_bytes = 4 * (math.prod(S.shape) + math.prod(z.shape))
    assert mem.alias_size_in_bytes >= state_bytes  # written where it lies
    assert mem.temp_size_in_bytes < 2**20  # the packed tiles and the walk's order


def test_retention_step_moves_the_state_through_the_kernel_alone(
    one_chip, compiled_not_interpreted
):
    """The scheduler's whole step at the cell's 20 slots of 8 layers: the
    kernel is in it, the state (5.5 GB: a second copy does not fit) is
    donated and aliased, and no other instruction gives a value as large as
    the stack, a layer of it or a slot's of a layer."""
    cfg, weights, state, slots = _retention_cell(one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip
    )
    compiled = kv_pager.paged_decode_step.lower(
        weights, i32(slots), i32(slots, 1), i32(slots), None, None, cfg,
        retention=state,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and rk.KERNEL_NAME in text
    assert pa.KERNEL_NAME not in text
    moved = [
        line[:200] for op, line in _state_values(text, state)
        if op not in ("parameter", "get-tuple-element", "tuple", "bitcast",
                      "while", "custom-call")
    ]
    assert not moved, moved
    mem = compiled.memory_analysis()
    state_bytes = sum(4 * math.prod(a.shape) for a in state)
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 2**28
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes < 14.5 * 2**30
    )


def test_retention_prefill_fits_beside_weights_and_state(
    one_chip, compiled_not_interpreted
):
    """The largest prefill dispatch (``retention.PREFILL_TOKENS`` tokens, a
    chunk of ``retention.CHUNK`` at a time): the state stays one buffer,
    only a slot's state of a layer is sliced out and written back, and the
    chunk's transients fit in what weights and state leave of 16 GB."""
    cfg, weights, state, _ = _retention_cell(one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip
    )
    compiled = kv_pager.paged_prefill.lower(
        weights, i32(1, retention.PREFILL_TOKENS), None, i32(1), None, None,
        cfg, slot=i32(1), retention=state, start=i32(1),
    ).compile()
    S = state[0].shape
    whole = [
        line[:200] for op, line in _state_values(compiled.as_text(), state)
        if op in ("copy", "fusion", "dynamic-slice")
        and f"[{','.join(map(str, S))}]" in line.split(" = ")[1].split("{")[0]
        and "dynamic-update-slice" not in line
    ]
    assert not whole, whole  # nothing copies or recomputes the whole stack
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(4 * math.prod(a.shape) for a in state)
    assert mem.temp_size_in_bytes < 2**30
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes < 15 * 2**30
    )


# ---------------------------------------------------------------------------
# the hybrid block (Falcon-H1): pages AND a float32 state a slot, a Mamba-2
# mixer beside attention, a kernel of its own for the state's step
# ---------------------------------------------------------------------------


def _hybrid_cell(sharding):
    """``(cfg, weights, pools, state, serve)`` of
    ``falcon_h1_34b_l4.decode_crowd`` as shapes on the described chip."""
    m = _load("configs", "falcon_h1_34b_l4")
    serve = _load("traffic", "decode_crowd")["serve"]
    dtype = jnp.dtype(m["dtype"])
    cfg = bridge_decode_falcon_h1.transformer_config(m, serve["max_seq"], dtype)
    slots, P = serve["max_slots"], serve["tokens_per_page"]
    max_pages = kv_pager.pages_for(serve["max_seq"], P)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            tree,
        )

    pool = jax.eval_shape(
        lambda: kv_pager.PagePool(
            cfg, slots * max_pages + 1, tokens_per_page=P, slots=slots
        ).k_pages
    )
    weights = on_chip(jax.eval_shape(
        lambda: kv_pager.serving_params(falcon_h1_decoder.make_weights(0, m, dtype), cfg)
    ))
    state = on_chip(jax.eval_shape(lambda: ssm.init_state(cfg, slots, dtype)))
    return cfg, weights, on_chip((pool, pool)), state, {**serve, "max_pages": max_pages}


def test_ssm_kernel_compiles_at_real_widths(one_chip, compiled_not_interpreted):
    cfg, _, _, (S, tail), serve = _hybrid_cell(one_chip)
    slots = serve["max_slots"]
    assert kv_pager.ssm_kernel_fits(cfg) and S.dtype == jnp.float32
    assert S.shape == (4, 128, 32, 128, 256) and tail.shape == (4, 128, 3, 5120)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(sk.ssm_step, donate_argnums=(6,)).lower(
        f32(slots, 32, 128), f32(slots, 2, 256), f32(slots, 2, 256), f32(slots, 32),
        f32(32), f32(32), S,
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and sk.KERNEL_NAME in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * math.prod(S.shape)  # written where it lies
    assert mem.temp_size_in_bytes < 2**24  # the packed tiles and the walk's order


def test_hybrid_step_runs_both_kernels_and_moves_no_state(
    one_chip, compiled_not_interpreted
):
    """The scheduler's whole step at the cell's 128 slots of 4 layers: both
    kernels are in it, pages and state (2.15 GB each) are donated and
    aliased, no instruction gives a value as large as the state's stack, a
    layer of it or a slot's of a layer, and the whole fits the chip."""
    cfg, weights, (kp, vp), state, serve = _hybrid_cell(one_chip)
    slots = serve["max_slots"]
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip
    )
    compiled = kv_pager.paged_decode_step.lower(
        weights, i32(slots), i32(slots, serve["max_pages"]), i32(slots), kp, vp,
        cfg, retention=state,
    ).compile()
    text = compiled.as_text()
    assert sk.KERNEL_NAME in text and pa.KERNEL_NAME in text
    moved = [
        line[:200] for op, line in _state_values(text, state)
        if op not in ("parameter", "get-tuple-element", "tuple", "bitcast",
                      "while", "custom-call")
    ]
    assert not moved, moved
    mem = compiled.memory_analysis()
    held = sum(a.dtype.itemsize * math.prod(a.shape) for a in (kp, vp, *state))
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2**29
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes < 14 * 2**30
    )


def test_hybrid_prefill_fits_beside_weights_pages_and_state(
    one_chip, compiled_not_interpreted
):
    """The largest prefill of the cell (a 1,024 bucket, SSD in chunks of
    128): pages and state stay where they lie, and the transients fit in
    what weights, pages and state leave of 16 GB."""
    cfg, weights, (kp, vp), state, serve = _hybrid_cell(one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip
    )
    compiled = kv_pager.paged_prefill.lower(
        weights, i32(1, 1024), i32(1, serve["max_pages"]), i32(1), kp, vp, cfg,
        slot=i32(1), retention=state,
    ).compile()
    S = state[0].shape
    whole = [
        line[:200] for op, line in _state_values(compiled.as_text(), state)
        if op in ("copy", "fusion", "dynamic-slice")
        and f"[{','.join(map(str, S))}]" in line.split(" = ")[1].split("{")[0]
        and "dynamic-update-slice" not in line
    ]
    assert not whole, whole  # nothing copies or recomputes the whole stack
    mem = compiled.memory_analysis()
    held = sum(a.dtype.itemsize * math.prod(a.shape) for a in (kp, vp, *state))
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2**30
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes < 15 * 2**30
    )


# ---------------------------------------------------------------------------
# the q, k and v projections (PR 42): read where they lie, a layer at a time
# ---------------------------------------------------------------------------

# `%constant_dynamic-slice_fusion.6 = bf16[1,4096,4096]{2,1,0:...} fusion(`,
# with the instruction's name and, for a transpose, its permutation
_NAMED = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([0-9,]*)\]\S* ([\w\-]+)\("
    r"(?:.*dimensions=\{([0-9,]*)\})?"
)


def _served(case, sharding, turned=True):
    """``(cfg, compiled)``: one serving executable of a cell, compiled for
    the described chip with the params the scheduler hands it (``turned``;
    a Mistral case also as ``make_weights`` holds them)."""
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=sharding
    )
    if case.startswith("mistral"):
        cfg, (w, toks, tables, indices, kp, vp), _ = _cell("mistral_7b_l8", sharding)
        if not turned:
            m = _load("configs", "mistral_7b_l8")
            w = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
                jax.eval_shape(lambda: transformer_decoder.make_weights(
                    0, m, jnp.dtype(m["dtype"]))),
            )
        if case == "mistral_step":
            lowered = kv_pager.paged_decode_step.lower(
                w, toks, tables, indices, kp, vp, cfg
            )
        else:
            bucket = int(case.rsplit("_", 1)[1])
            lowered = kv_pager.paged_prefill.lower(
                w, i32(1, bucket), i32(1, tables.shape[1]), i32(1), kp, vp, cfg
            )
    elif case == "retention_step":
        cfg, w, state, slots = _retention_cell(sharding)
        lowered = kv_pager.paged_decode_step.lower(
            w, i32(slots), i32(slots, 1), i32(slots), None, None, cfg,
            retention=state,
        )
    else:
        cfg, w, (kp, vp), state, serve = _hybrid_cell(sharding)
        slots = serve["max_slots"]
        lowered = kv_pager.paged_decode_step.lower(
            w, i32(slots), i32(slots, serve["max_pages"]), i32(slots), kp, vp,
            cfg, retention=state,
        )
    return cfg, lowered.compile()


def _projection_values(text, cfg):
    """``(name, opcode, dims, permutation, line)`` of every instruction
    whose result has the shape of one layer's q, k or v projection, in
    either orientation (leading unit axes dropped)."""
    d = cfg.d_model
    outs = {cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim}
    shapes = {(o, d) for o in outs} | {(d, o) for o in outs}
    for line in text.splitlines():
        m = _NAMED.match(line)
        if not m or not m.group(2):
            continue
        dims = tuple(int(x) for x in m.group(2).split(","))
        core = dims[next((i for i, x in enumerate(dims) if x != 1), len(dims)):]
        if core in shapes:
            yield m.group(1), m.group(3), dims, m.group(4), line.strip()


def _projection_moves(text, cfg):
    """The instructions that turn a layer's projection to another layout:
    a copy, a transpose that permutes, or a fusion named after either (the
    parent's ``copy.19`` gave ``bf16[1,4096,4096]{1,2,0}`` from each
    layer's ``wq``, ``copy.21`` and ``copy.22`` its ``wk`` and ``wv``)."""
    return [
        line[:200] for name, op, _, perm, line in _projection_values(text, cfg)
        if op == "copy"
        or (op == "transpose" and perm is not None
            and perm.split(",") != sorted(perm.split(","), key=int))
        or (op == "fusion" and ("copy" in name or "transpose" in name))
    ]


_SERVED = ("mistral_step", "mistral_prefill_128", "mistral_prefill_1024",
           "retention_step", "hybrid_step")


@pytest.mark.parametrize("case", _SERVED)
def test_projections_are_read_where_they_lie(case, one_chip, compiled_not_interpreted):
    """The served step and prefills of every block that projects through
    ``transformer._attn_qkv`` or ``retention.project``: no instruction turns
    a layer's q, k or v projection to another layout (48 MB a layer at
    Mistral's widths, 70 MB at Brumby's, 35 MB at Falcon-H1's, every step),
    and each is still read once a layer: the scan's slice of the stack at
    the layer, ``[1, out, in]``, into the product as it lies."""
    cfg, compiled = _served(case, one_chip)
    text = compiled.as_text()
    moved = _projection_moves(text, cfg)
    assert not moved, moved
    read = {
        dims for name, op, dims, _, _ in _projection_values(text, cfg)
        if op == "fusion" and "dynamic-slice" in name
    }
    d = cfg.d_model
    for out in (cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim):
        assert (1, out, d) in read, (out, read)
        if out != d:  # the stack as it used to be held is read nowhere
            assert (1, d, out) not in read, read


def test_projections_held_in_by_out_are_copied_every_layer(
    one_chip, compiled_not_interpreted
):
    """Why the scheduler turns them (``kv_pager.serving_params``): held
    ``[L, in, out]`` as ``make_weights`` and training keep them, each
    layer's slice of ``wq``, ``wk`` and ``wv`` is copied to the layout the
    dot reads before the product.  If this starts failing the turn can go."""
    cfg, compiled = _served("mistral_step", one_chip, turned=False)
    moved = _projection_moves(compiled.as_text(), cfg)
    assert len(moved) == 3, moved


# ---------------------------------------------------------------------------
# window layers among full ones (Trinity, PR 43): the earlier blocks' serving
# executables lower as they did, and Trinity's compile and fit
# ---------------------------------------------------------------------------

# sha256 of each earlier block's serving executables as they lowered before
# the layer pattern, the window, the QK-norm, the gate, the sandwich norms and
# the selection bias came (at the benchmark's widths, the prefill at a 256
# bucket, x64 off), each Mosaic kernel's serialized body read back without its
# debug locations, which name the kernel's source lines
_LOWERED_BEFORE = {
    "mistral_7b_l8.step": "3a6157aef366b3b6c6b37e60203687ae71db0b09e25c63dbc367ab6f9c28a594",
    "mistral_7b_l8.prefill": "4b80168b62fd66ed0af1b11c1ca3d006c0c216eaf4522088b89198af0380963c",
    "zaya1_8b_l20.step": "92c5de685849258b46c865759f32bd36970497f7257f1a9762ae2d2248ac9d16",
    "zaya1_8b_l20.prefill": "b49c43fe17252cb75f98712e1d4049863f37031656e38b9683b92af72c389566",
    # since its decode attention reads each row's pages in place through the
    # kernel's latent form (``tfs_latent_attention``)
    "axk1_l7_ep16.step": "5cff429d6e240692d511e4bd40b92ca692e2796d41b124d7fd37ef7531fd88e9",
    "axk1_l7_ep16.prefill": "b3c7b07ca3b80b5de308e98b90293617f8119cb872aac60ec1b867f540af2ef7",
    "brumby_14b_l8.step": "b08e671b512705bc83a8c54901c7370f696f7b49346f4bd3ecd605b48e7945fb",
    "brumby_14b_l8.prefill": "eb3928fc0d5bd81e53aa9505c1f13ec8d62d785251fa7825ddd1984460584180",
    "falcon_h1_34b_l4.step": "cf1f0e92ab7567a8abe976e2a7c57134bafe467e0bf38a83cb322b848843cd99",
    "falcon_h1_34b_l4.prefill": "3066bbd31568be798312c0ac7f171f89a6a9f95243c31bd5ce9bf9c54844524b",
}
_KERNEL_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def _without_locations(text):
    """The lowered text, each Mosaic kernel's body replaced by the hash of
    its program without debug locations."""
    import base64
    import hashlib

    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True

    def body(m):
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(2)))
        asm = module.operation.get_asm(enable_debug_info=False)
        return m.group(1) + hashlib.sha256(asm.encode()).hexdigest() + m.group(3)

    return _KERNEL_BODY.sub(body, text)


def _lowered(case, sharding):
    """One serving executable of an earlier block, lowered as the cell's
    scheduler calls it."""
    config, which = case.rsplit(".", 1)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=sharding
    )
    if config in ("mistral_7b_l8", "zaya1_8b_l20", "axk1_l7_ep16"):
        cfg, (w, toks, tables, idx, kp, vp), kw = _cell(config, sharding)
        if which == "step":
            return kv_pager.paged_decode_step.lower(w, toks, tables, idx, kp, vp, cfg, **kw)
        kw = {**kw, "slot": i32(1)} if kw else kw
        return kv_pager.paged_prefill.lower(
            w, i32(1, 256), i32(1, tables.shape[1]), i32(1), kp, vp, cfg, **kw
        )
    if config == "brumby_14b_l8":
        cfg, w, state, slots = _retention_cell(sharding)
        if which == "step":
            return kv_pager.paged_decode_step.lower(
                w, i32(slots), i32(slots, 1), i32(slots), None, None, cfg, retention=state
            )
        return kv_pager.paged_prefill.lower(
            w, i32(1, 256), None, i32(1), None, None, cfg, slot=i32(1),
            retention=state, start=i32(1),
        )
    cfg, w, (kp, vp), state, serve = _hybrid_cell(sharding)
    slots, width = serve["max_slots"], serve["max_pages"]
    if which == "step":
        return kv_pager.paged_decode_step.lower(
            w, i32(slots), i32(slots, width), i32(slots), kp, vp, cfg, retention=state
        )
    return kv_pager.paged_prefill.lower(
        w, i32(1, 256), i32(1, width), i32(1), kp, vp, cfg, slot=i32(1), retention=state
    )


@pytest.mark.parametrize("case", sorted(_LOWERED_BEFORE))
def test_earlier_blocks_lower_as_they_did(case, one_chip, compiled_not_interpreted):
    """The dense, CCA, latent, retention and hybrid blocks' decode step and
    prefill, program text for program text: nothing Trinity's stack brought
    traces an operation into them."""
    import hashlib

    text = _without_locations(_lowered(case, one_chip).as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == _LOWERED_BEFORE[case]


def _trinity_cell(sharding):
    """``(cfg, weights, (kp, vp), serve, width)`` of
    ``trinity_large_l5_ep8.decode_longmix`` as shapes on the described chip:
    the pools are the pairs of the full and the window layers'."""
    from perfbench.drivers import bridge_decode_trinity
    from perfbench.refs import trinity_decoder

    m = _load("configs", "trinity_large_l5_ep8")
    serve = _load("traffic", "decode_longmix")["serve"]
    dtype = jnp.dtype(m["dtype"])
    cfg = bridge_decode_trinity.transformer_config(m, serve["max_seq"], dtype)
    P = serve["tokens_per_page"]

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            tree,
        )

    pools = jax.eval_shape(lambda: kv_pager.PagePool(
        cfg, serve["pool_pages"], tokens_per_page=P, slots=serve["max_slots"]
    ).k_pages)
    weights = on_chip(jax.eval_shape(lambda: kv_pager.serving_params(
        trinity_decoder.make_weights(0, m, dtype), cfg
    )))
    width = kv_pager.pages_for(serve["max_seq"], P) + kv_pager.ring_pages(cfg, P)
    return cfg, weights, (on_chip(pools), on_chip(pools)), serve, width


def test_trinity_step_runs_the_kernel_over_both_pools(one_chip, compiled_not_interpreted):
    """The 48-slot step: the paged kernel for the full layer and over the
    rings for the window layers, both pools aliased to their arguments, and
    nothing of a pool's size copied."""
    cfg, w, (kp, vp), serve, width = _trinity_cell(one_chip)
    slots = serve["max_slots"]
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip
    )
    compiled = kv_pager.paged_decode_step.lower(
        w, i32(slots), i32(slots, width), i32(slots), kp, vp, cfg
    ).compile()
    text = compiled.as_text()
    assert text.count(pa.KERNEL_NAME) >= 2  # the full layer's run and the window layers'
    for pool in kp:
        _pool_keeps_its_layout(text, pool.shape)
    mem = compiled.memory_analysis()
    held = sum(a.dtype.itemsize * math.prod(a.shape) for a in (*kp, *vp))
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 2**28


def test_trinity_longest_prefill_fits_beside_weights_and_pools(
    one_chip, compiled_not_interpreted
):
    """The 16,384 bucket's prefill: flash attention by kind, the grouped
    expert products a run of tokens at a time, and all of it within what
    the weights and both pools leave of the chip."""
    from tensorframes_tpu.parallel import flash

    cfg, w, (kp, vp), serve, width = _trinity_cell(one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip
    )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(flash, "_resolve_interpret", lambda interpret: False)
        compiled = kv_pager.paged_prefill.lower(
            w, i32(1, 16384), i32(1, width), i32(1), kp, vp, cfg, slot=i32(1)
        ).compile()
    assert flash.PREFILL_KERNEL_NAME in compiled.as_text()
    mem = compiled.memory_analysis()
    held = sum(a.dtype.itemsize * math.prod(a.shape) for a in (*kp, *vp))
    assert mem.alias_size_in_bytes >= held
    # the weights' q, k, v and gate are also held as they were made, beside
    # the turned ones the executables read: 0.44 GB more on the chip
    assert (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes + 0.44e9 < 15.5 * 2**30
    )
