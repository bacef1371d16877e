#!/usr/bin/env python3
"""Chip smoke: the repo's main paths, once, on the TPU, at full width.

    python3 chip_smoke.py

One process (one process owns the chip; the bridge server below is a
thread of it).  Each phase goes through the entry point a user calls,
checks what came back by the repo's own means, and prints one JSON line;
the last line of stdout is the verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exit status is 0 only when every phase passed.  Without a TPU backend the
script exits 2 before any phase runs and prints no result: it never sets
``JAX_PLATFORMS``, never catches a backend-initialisation failure, and
every device array a phase hands back is checked to live on the chip.

Phases (widths in :class:`Sizes`; depth is cut, weights come from a seed):

* ``verbs``  — full Inception-v3 through ``tfs.map_blocks`` on an uncached
  host frame (prefetched blocks, donated inputs), again after ``.cache()``
  (the non-donating executable), a provably row-independent program over
  the same frame (the chunk-streamed path), a ``reduce_blocks`` over the
  output, and 8 rows against a float32 run on the host CPU device.
* ``bridge_map`` / ``bridge_decode`` — ``bridge.serve`` + ``BridgeClient``
  in this process: the frozen Inception GraphDef sent as bytes, then
  concurrent ``decode`` RPCs through ``DecodeScheduler`` and ``kv_pager``.
* ``train``  — ``train.fit`` from a ``FrameLoader`` on the ~151M model.
* ``flash``  — the Pallas kernels compiled (``interpret=False``), forward
  and ``jax.grad``, against ``parallel.ring.full_attention``.

With two or more local devices the script widens itself: the verbs phase
must put blocks and cache shards on every device, training runs under a
dp x tp mesh, and ``ring_flash`` compiles under ``sp=2``.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at
``<checkout>/.cache/jax`` (``tensorframes_tpu.compile_cache``).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at.  The defaults are the full widths the chip
    check asks for; the CPU tests shrink them to drive the control flow."""

    # verbs: 512 uint8 rows of 299*299*3 = 137 MB a block, above twice the
    # 64 MB h2d chunk, so a block streams in chunks when the engine may
    verb_block_rows: int = 512
    verb_blocks: int = 2
    ref_rows: int = 8
    bridge_rows: int = 256
    bridge_requests: int = 3
    # the bench config-6/8 transformer (~151M): full width, full depth
    lm: Tuple[Tuple[str, Any], ...] = (
        ("vocab_size", 8192),
        ("d_model", 1024),
        ("n_layers", 8),
        ("n_heads", 16),
        ("n_kv_heads", 16),
        ("d_ff", 4096),
        ("max_seq", 2048),
    )
    train_batch: int = 8
    train_seq: int = 2048
    train_steps: int = 4
    # two prompt lengths in ONE prefill bucket (64), so which requests are
    # admitted together cannot change which executable runs
    decode_prompts: Tuple[int, ...] = (40, 60, 40, 60)
    decode_new: int = 32
    # (B, L, H, KVH, Dh)
    flash_shapes: Tuple[Tuple[int, ...], ...] = (
        (2, 2048, 16, 16, 64),
        (1, 8192, 16, 4, 128),
    )
    ring_layers: int = 2


# Tolerances, each set a few times above what the v5e showed (my chip
# runs, PR 21; the scores of different rows differ by ~0.3):
#   bf16 Inception on the chip vs float32 on the host CPU, max |score diff|
#   (measured 0.0023; 7 of 8 top-1 classes agree, the eighth is a near-tie)
REF_SCORE_ATOL = 0.02
#   f32 frozen graph (bf16 MXU passes) vs the bf16 native program, same rows
#   (measured 0.0064; 254 of 256 top-1 classes agree)
GRAPH_SCORE_ATOL = 0.03
#   flash vs full attention, max |diff| / max |reference|, bf16
#   (measured 0.003-0.008 over forward, dq, dk, dv)
FLASH_RTOL = 3e-2


class SmokeFailure(AssertionError):
    """A phase check that did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _lm_cfg(sz: Sizes, **over):
    import jax.numpy as jnp

    from tensorframes_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        **{**dict(sz.lm), "dtype": jnp.bfloat16, **over}
    )


def check_on_chip(tree, platform: str, what: str) -> None:
    """Every jax array in ``tree`` lives on devices of ``platform``."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            plats = {d.platform for d in leaf.devices()}
            check(
                plats == {platform},
                f"{what}: array on {sorted(plats)}, expected {platform}",
            )


def make_images(n: int, seed: int = 0):
    """``n`` uint8 images whose brightness range differs row to row, so
    rows score differently and a row mix-up cannot pass the comparisons."""
    import numpy as np

    from tensorframes_tpu.models import inception

    side = inception.INPUT_SIZE
    rng = np.random.RandomState(seed)
    out = np.empty((n, side, side, 3), np.uint8)
    hi = rng.randint(16, 256, size=n)
    for lo in range(0, n, 64):  # bounded temporaries
        part = hi[lo : lo + 64]
        raw = rng.randint(0, 256, size=(len(part), side, side, 3))
        out[lo : lo + 64] = (raw * part[:, None, None, None]) >> 8
    return out


# ---------------------------------------------------------------------------
# phases — each takes (ctx, sz) and returns the details for its record
# ---------------------------------------------------------------------------


def phase_verbs(ctx: Dict[str, Any], sz: Sizes) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import tensorframes_tpu as tfs
    from tensorframes_tpu import observability as obs
    from tensorframes_tpu.models import inception
    from tensorframes_tpu.ops import frame_cache

    n_dev = jax.local_device_count()
    # widened: one block per device at least, so every device can be asked
    # to have executed one
    n_blocks = max(sz.verb_blocks, n_dev if n_dev >= 2 else 0)
    images = make_images(n_blocks * sz.verb_block_rows)
    params = inception.init(0, dtype=jnp.bfloat16)
    scoring = tfs.Program.wrap(
        inception.scoring_program(params, dtype=jnp.bfloat16),
        fetches=["prediction", "score"],
    )
    # The engine streams a block in chunks only when it can prove the
    # program row-independent, and its classifier has no rule for
    # convolutions: Inception blocks go up whole (block-level prefetch and
    # donation).  This second program is provable, so the same 137 MB
    # blocks stream through engine._run_block_streamed; int32 sums are
    # exact, so numpy is its reference.
    pixel_sum = tfs.Program.wrap(
        lambda image: {"pixel_sum": image.astype(jnp.int32).sum(axis=(1, 2, 3))},
        fetches=["pixel_sum"],
    )
    frame = tfs.TensorFrame.from_arrays({"image": images}, num_blocks=n_blocks)

    def run(program, fr, names):
        """``map_blocks`` under a span and a request ledger."""
        obs.enable()
        try:
            with obs.request_ledger() as led:
                t0 = time.perf_counter()
                out = tfs.map_blocks(program, fr)
                cols = [out.column(n).data for n in names]
                check_on_chip(cols, ctx["platform"], "map_blocks output")
                host = [np.asarray(c) for c in jax.device_get(cols)]
                wall = time.perf_counter() - t0
            span = obs.last_spans(1)[0]
            return out, host, span.get("prefetch", {}), led.snapshot(), wall
        finally:
            obs.disable()

    # 1. uncached host frame: prefetched, freshly staged inputs donated
    out1, (pred1, score1), pf, led1, wall = run(
        scoring, frame, ("prediction", "score")
    )
    details: Dict[str, Any] = {
        "rows": int(frame.num_rows),
        "blocks": n_blocks,
        "uncached_s": round(wall, 2),
        "donated": bool(pf["donate"]),
        "inception_streamed": pf["items"] > n_blocks,
    }
    check(
        pred1.shape == (frame.num_rows,) and score1.shape == pred1.shape,
        f"output shapes {pred1.shape} {score1.shape}",
    )
    check(np.isfinite(score1).all(), "non-finite scores")
    check(((pred1 >= 0) & (pred1 < inception.NUM_CLASSES)).all(), "class range")
    # the input column rides along in the output and is still readable
    # after its staged copies were donated
    check(
        np.array_equal(np.asarray(out1.column("image").data), images),
        "appended input column differs",
    )
    _, (sum1,), spf, _, _ = run(pixel_sum, frame, ("pixel_sum",))
    details["streamed_chunks"] = spf["items"]
    check(
        np.array_equal(sum1, images.reshape(len(images), -1).sum(1)),
        "streamed pixel sums differ from numpy",
    )
    if ctx["full_width"]:
        check(pf["donate"] and spf["donate"], f"no donation: {pf} {spf}")
        check(spf["items"] > n_blocks, f"blocks were not chunk-streamed: {spf}")

    if n_dev >= 2:
        per_dev = led1["blocks_per_device"]
        details["blocks_per_device"] = per_dev
        check(
            len(per_dev) == n_dev and all(v > 0 for v in per_dev.values()),
            f"not every device executed a block: {per_dev}",
        )

    # 2. the same frame cached in HBM: the non-donating executables
    cached = frame.cache()
    if n_dev >= 2:
        cache = frame_cache.active_cache(cached)
        check(cache is not None, "cache() built no sharded cache")
        resident = cache.resident_bytes_per_device()
        details["cache_bytes_per_device"] = resident
        check(
            len(resident) == n_dev and all(b > 0 for b in resident),
            f"cache shards missing on some device: {resident}",
        )
    else:
        check_on_chip(
            [c.data for c in cached.columns], ctx["platform"], "cached frame"
        )
    _, (pred2, score2), pf2, _, wall = run(
        scoring, cached, ("prediction", "score")
    )
    _, (sum2,), spf2, _, _ = run(pixel_sum, cached, ("pixel_sum",))
    details["cached_s"] = round(wall, 2)
    check(
        not pf2.get("donate") and not spf2.get("donate"),
        "a cached frame's columns were donated",
    )
    details["bit_identical"] = bool(
        np.array_equal(pred1, pred2)
        and np.array_equal(score1, score2)
        and np.array_equal(sum1, sum2)
    )
    check(
        details["bit_identical"],
        "donated-uncached and cached runs differ: max |score diff| "
        f"{np.abs(score1 - score2).max()}, "
        f"{int((pred1 != pred2).sum())} predictions",
    )

    # 3. a reduce over the map's output
    red = tfs.reduce_blocks(
        lambda score_input: {"score": score_input.max(0)}, out1
    )
    check_on_chip(red, ctx["platform"], "reduce_blocks output")
    check(
        float(np.asarray(red["score"])) == float(score1.max()),
        f"reduce_blocks max {red['score']} != {score1.max()}",
    )

    # 4. float32 reference on the host CPU device, same program
    k = sz.ref_rows
    cpu = jax.devices("cpu")[0]
    cpu_params = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    with jax.default_device(cpu):
        ref = jax.jit(inception.scoring_program(cpu_params, dtype=jnp.float32))(
            jax.device_put(images[:k], cpu)
        )
        ref_score = np.asarray(ref["score"])
        ref_pred = np.asarray(ref["prediction"])
    diff = float(np.abs(score1[:k] - ref_score).max())
    details["ref_score_max_abs_diff"] = round(diff, 5)
    details["ref_score_atol"] = REF_SCORE_ATOL
    details["ref_score_spread"] = round(float(np.ptp(ref_score)), 4)
    details["ref_predictions_equal"] = f"{int((pred1[:k] == ref_pred).sum())}/{k}"
    check(diff <= REF_SCORE_ATOL, f"f32 reference: |score diff| {diff}")

    ctx["params"] = params
    ctx["images"] = images[: sz.bridge_rows].copy()  # not a view of them all
    ctx["scores"] = score1[: sz.bridge_rows]
    ctx["predictions"] = pred1[: sz.bridge_rows]
    return details


def _serve(ctx: Dict[str, Any], sz: Sizes):
    """The one bridge server of the run (a thread of this process), with
    the warm program pool and the decode model a deployment would give it."""
    if "server" not in ctx:
        import jax

        from tensorframes_tpu import bridge
        from tensorframes_tpu.models import transformer as tfm

        cfg = _lm_cfg(sz)
        params = tfm.init(jax.random.PRNGKey(0), cfg)
        ctx["lm_cfg"], ctx["lm_params"] = cfg, params
        ctx["server"] = bridge.serve(
            background=True,
            warm_spec="4",
            decode_model={"params": params, "cfg": cfg},
        )
    return ctx["server"]


def phase_bridge_map(ctx: Dict[str, Any], sz: Sizes) -> Dict[str, Any]:
    import numpy as np

    from tensorframes_tpu import observability as obs
    from tensorframes_tpu.bridge import BridgeClient
    from tensorframes_tpu.models import inception
    from tensorframes_tpu.models.inception_export import export_graphdef

    check("params" in ctx, "needs the verbs phase's parameters and scores")
    server = _serve(ctx, sz)
    graph = export_graphdef(inception.fold_bn(ctx["params"]))
    images = ctx["images"]
    client = BridgeClient(*server.address, tenant="smoke")
    try:
        rf = client.create_frame({"image_data": images}, num_blocks=2)
        runs = []
        for _ in range(sz.bridge_requests):
            c0 = obs.counters()
            t0 = time.perf_counter()
            out = rf.map_blocks(
                graph, fetches=["prediction", "score"],
                inputs={"image": "image_data"},
            )
            cols = out.collect(["prediction", "score"])
            out.release()
            d = obs.counters_delta(c0)
            runs.append(
                {
                    "s": round(time.perf_counter() - t0, 2),
                    "traces": d["program_traces"],
                    "compiles": d["backend_compiles"],
                    "warm_hits": d["warm_program_hits"],
                    "score": np.asarray(cols["score"]),
                    "prediction": np.asarray(cols["prediction"]),
                }
            )
    finally:
        client.close()
    first = runs[0]
    check(first["score"].shape == (len(images),), "bridge score shape")
    for r in runs[1:]:
        check(
            np.array_equal(r["score"], first["score"])
            and np.array_equal(r["prediction"], first["prediction"]),
            "bridge requests disagree with each other",
        )
        check(r["warm_hits"] == 1, f"warm pool missed: {r['warm_hits']}")
        check(r["traces"] == 0, f"warm request re-traced: {r['traces']}")
    diff = float(np.abs(first["score"] - ctx["scores"]).max())
    check(diff <= GRAPH_SCORE_ATOL, f"graph vs native: |score diff| {diff}")
    return {
        "rows": len(images),
        "graph_bytes": len(graph),
        "requests": [
            {k: r[k] for k in ("s", "traces", "compiles", "warm_hits")}
            for r in runs
        ],
        "vs_native_score_max_abs_diff": round(diff, 5),
        "vs_native_score_atol": GRAPH_SCORE_ATOL,
        "vs_native_predictions_equal": (
            f"{int((first['prediction'] == ctx['predictions']).sum())}"
            f"/{len(images)}"
        ),
    }


def phase_bridge_decode(ctx: Dict[str, Any], sz: Sizes) -> Dict[str, Any]:
    import jax.numpy as jnp
    import numpy as np

    from tensorframes_tpu import observability as obs
    from tensorframes_tpu.bridge import BridgeClient
    from tensorframes_tpu.models import decode

    server = _serve(ctx, sz)
    cfg, params = ctx["lm_cfg"], ctx["lm_params"]
    sched = server.decode_scheduler
    rng = np.random.RandomState(8)
    prompts = [
        rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
        for n in sz.decode_prompts
    ]

    def one_round() -> List[List[int]]:
        outs: List[Any] = [None] * len(prompts)

        def worker(i: int) -> None:
            try:
                c = BridgeClient(*server.address, tenant=f"t{i % 2}")
                try:
                    outs[i] = c.decode(prompts[i], max_new=sz.decode_new)
                finally:
                    c.close()
            except Exception as e:  # re-raised on the caller's thread below
                outs[i] = e

        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
            check(not t.is_alive(), "a decode request hung")
        for o in outs:
            if isinstance(o, BaseException):
                raise o
        return [list(o["tokens"]) for o in outs]

    c0 = obs.counters()
    t0 = time.perf_counter()
    first = one_round()
    first_s = time.perf_counter() - t0
    c1 = obs.counters()
    t0 = time.perf_counter()
    second = one_round()
    second_s = time.perf_counter() - t0
    steady = obs.counters_delta(c1)
    total = obs.counters_delta(c0)

    for toks in first:
        check(len(toks) == sz.decode_new, f"{len(toks)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in toks), "token range")
    check(first == second, "two identical rounds decoded differently")
    check(steady["program_traces"] == 0, f"steady state traced: {steady}")
    check(
        steady["backend_compiles"] == 0,
        f"steady state compiled {steady['backend_compiles']} executables",
    )
    snap = sched.snapshot()
    check(snap["pages_used"] == 0, f"pages still held: {snap['pages_used']}")
    check(
        total["kv_pages_allocated"] == total["kv_pages_freed"]
        and total["kv_pages_allocated"] > 0,
        f"page accounting: {total['kv_pages_allocated']} allocated, "
        f"{total['kv_pages_freed']} freed",
    )
    check_on_chip(
        (sched._kp, sched._vp), ctx["platform"], "kv page pool"
    )

    # reported, not gated: bit-identity of paged vs contiguous decode was
    # only ever pinned in f32 on XLA:CPU
    equal = 0
    for p, toks in zip(prompts, first):
        ref = decode.generate(
            params, jnp.asarray(p[None]), cfg, sz.decode_new,
            cache_len=sched.cap,
        )
        check_on_chip(ref, ctx["platform"], "decode.generate output")
        equal += int(np.asarray(ref)[0, p.size :].tolist() == toks)
    return {
        "requests": len(prompts),
        "prompt_lengths": sorted(set(sz.decode_prompts)),
        "new_tokens": sz.decode_new,
        "slots": sched.max_slots,
        "cap_tokens": sched.cap,
        "first_round_s": round(first_s, 2),
        "second_round_s": round(second_s, 2),
        "steady_backend_compiles": steady["backend_compiles"],
        "kv_pages_allocated": total["kv_pages_allocated"],
        "paged_equals_contiguous": f"{equal}/{len(prompts)}",
    }


def _close_server(ctx: Dict[str, Any]) -> None:
    server = ctx.pop("server", None)
    if server is not None:
        server.close()  # drains, and closes the decode scheduler with it
    ctx.pop("lm_params", None)


def phase_train(ctx: Dict[str, Any], sz: Sizes) -> Dict[str, Any]:
    import jax
    import numpy as np

    import tensorframes_tpu as tfs
    from tensorframes_tpu import train
    from tensorframes_tpu.parallel.mesh import training_mesh

    _close_server(ctx)  # the train state needs the HBM the page pool held
    cfg = _lm_cfg(sz, remat_policy="selective")
    rng = np.random.RandomState(6)
    tokens = rng.randint(
        0, cfg.vocab_size, size=(sz.train_batch, sz.train_seq + 1)
    ).astype(np.int32)
    frame = tfs.TensorFrame.from_arrays({"tokens": tokens})
    n_dev = jax.local_device_count()

    def fit(mesh=None, cfg=cfg, steps=sz.train_steps):
        loader = tfs.FrameLoader(frame, batch_size=sz.train_batch, mesh=mesh)
        params, _, losses = train.fit(
            loader, cfg, train.TrainConfig(), steps=steps
        )
        check_on_chip(params, ctx["platform"], "trained parameters")
        check(np.isfinite(losses).all(), f"non-finite loss: {losses}")
        return params, losses

    details: Dict[str, Any] = {
        "batch": sz.train_batch, "seq": sz.train_seq, "steps": sz.train_steps,
    }
    if n_dev >= 2 and n_dev % 2 == 0:
        devices = set(jax.local_devices())
        mesh = training_mesh(dp=n_dev // 2, tp=2)
        with jax.set_mesh(mesh):
            params, losses = fit(mesh)
            wq = params["blocks"]["wq"]
            held = {s.device for s in wq.addressable_shards}
            check(held == devices, f"tp-sharded wq lives on {len(held)} devices")
            check(
                wq.addressable_shards[0].data.shape[-1] * 2 == wq.shape[-1],
                f"wq is not split over tp: {wq.sharding}",
            )
            in_use = {
                str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                for d in devices
            }
            if ctx["full_width"]:  # XLA:CPU reports no memory stats
                check(all(in_use.values()), f"idle device: {in_use}")
        details["mesh"] = {"dp": n_dev // 2, "tp": 2}
        details["bytes_in_use_per_device"] = in_use
        del params, wq
        gc.collect()
        # sequence parallelism: the Pallas ring step compiled under sp=2
        ring_cfg = dataclasses.replace(
            cfg, attn_impl="ring_flash", n_layers=sz.ring_layers
        )
        ring_mesh = training_mesh(dp=n_dev // 2, sp=2)
        with jax.set_mesh(ring_mesh):
            _, ring_losses = fit(ring_mesh, ring_cfg, steps=2)
        details["ring_flash_sp2_losses"] = [round(l, 4) for l in ring_losses]
    else:
        _, losses = fit()
    details["losses"] = [round(l, 4) for l in losses]
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return details


def phase_flash(ctx: Dict[str, Any], sz: Sizes) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorframes_tpu.parallel.flash import flash_attention
    from tensorframes_tpu.parallel.ring import full_attention

    def rel_err(a, b) -> float:
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))

    # interpret=False on the chip: the Mosaic compile or an error, never
    # the interpreter (the CPU tests pass True to drive the control flow)
    interpret = not ctx["full_width"]
    shapes = []
    for B, L, H, KVH, Dh in sz.flash_shapes:
        ks = jax.random.split(jax.random.PRNGKey(L), 4)
        q = jax.random.normal(ks[0], (B, L, H, Dh), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, L, KVH, Dh), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, L, KVH, Dh), jnp.bfloat16)
        w = jax.random.normal(ks[3], (B, L, H, Dh), jnp.bfloat16)
        g = H // KVH

        def flash_loss(q, k, v):
            o = flash_attention(q, k, v, True, 128, 128, interpret)
            return (o.astype(jnp.float32) * w).sum(), o

        def ref_loss(q, k, v):
            o = full_attention(
                q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2), True
            )
            return (o.astype(jnp.float32) * w).sum(), o

        f_grads, f_out = jax.jit(
            jax.grad(flash_loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
        r_grads, r_out = jax.jit(
            jax.grad(ref_loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
        check_on_chip((f_out, f_grads), ctx["platform"], "flash outputs")
        errs = {
            "fwd": rel_err(f_out, r_out),
            **{
                f"d{n}": rel_err(fg, rg)
                for n, fg, rg in zip("qkv", f_grads, r_grads)
            },
        }
        for name, e in errs.items():
            check(
                np.isfinite(e) and e <= FLASH_RTOL,
                f"flash {name} at {(B, L, H, KVH, Dh)}: rel err {e}",
            )
        shapes.append(
            {
                "shape": [B, L, H, KVH, Dh],
                **{n: round(e, 5) for n, e in errs.items()},
            }
        )
        del q, k, v, w, f_grads, f_out, r_grads, r_out
    return {"interpret": interpret, "rtol": FLASH_RTOL, "shapes": shapes}


PHASES: Sequence[Tuple[str, Callable[[Dict[str, Any], Sizes], Dict[str, Any]]]] = (
    ("verbs", phase_verbs),
    ("bridge_map", phase_bridge_map),
    ("bridge_decode", phase_bridge_decode),
    ("train", phase_train),
    ("flash", phase_flash),
)


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------


def run_phases(
    phases, ctx: Dict[str, Any], sz: Sizes, emit: Callable[[str], None] = print
) -> List[str]:
    """Run every phase, one JSON line each; returns the names that failed.
    A failure is recorded with its traceback on stderr and the run goes on
    (the later lines are worth their chip time), but the caller must turn a
    non-empty return into a non-zero exit."""
    import jax

    from tensorframes_tpu import observability as obs
    from tensorframes_tpu import train

    dev = jax.devices()[0]
    failed: List[str] = []
    for name, fn in phases:
        c0 = obs.counters()
        t0 = time.perf_counter()
        rec: Dict[str, Any] = {"phase": name}
        try:
            details = fn(ctx, sz)
            rec["ok"] = True
        except Exception as e:  # phase boundary: record, go on, exit != 0
            traceback.print_exc()
            details = {"error": f"{type(e).__name__}: {e}"[:400]}
            rec["ok"] = False
            failed.append(name)
        d = obs.counters_delta(c0)
        rec.update(
            platform=dev.platform,
            device_kind=dev.device_kind,
            n_devices=len(jax.devices()),
            wall_s=round(time.perf_counter() - t0, 2),
            backend_compiles=d["backend_compiles"],
            persistent_cache_hits=d["persistent_cache_hits"],
            persistent_cache_misses=d["persistent_cache_misses"],
            hbm_peak_bytes=train.hbm_high_water(),
            **details,
        )
        emit(json.dumps(rec))
        # 16 GB holds Inception, a cached frame, the train state and a page
        # pool in turn, not together
        gc.collect()
        jax.clear_caches()
    _close_server(ctx)
    return failed


def run(phases, sz: Sizes, ctx: Dict[str, Any], complete: bool = True) -> int:
    """Run ``phases``, print a summary line and then the verdict as the
    last line, and return the process's exit status: 0 only when every
    phase of the whole list ran and passed.  The verdict holds exactly
    ``ok`` and ``device`` (the chip check reads it strictly); everything
    else about the run is on the summary line before it."""
    import jax

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    failed = run_phases(phases, ctx, sz, emit=lambda s: print(s, flush=True))
    summary: Dict[str, Any] = {
        "phase": "summary",
        "wall_s": round(time.perf_counter() - t0, 1),
        "failed": failed,
    }
    if not complete:
        summary["partial"] = [name for name, _ in phases]
    print(json.dumps(summary), flush=True)
    verdict = {
        "ok": not failed and complete,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


def main(argv: Sequence[str]) -> int:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(
            f"chip_smoke: no TPU — jax.default_backend() is {backend!r} "
            f"(devices: {jax.devices()}); this script only runs on the chip",
            file=sys.stderr,
        )
        return 2

    import tensorframes_tpu  # from this checkout: HERE leads sys.path
    from tensorframes_tpu import compile_cache, native

    dev = jax.devices()[0]
    print(
        json.dumps(
            {
                "phase": "start",
                "platform": dev.platform,
                "device_kind": dev.device_kind,
                "n_devices": len(jax.devices()),
                "jax": jax.__version__,
                "package": os.path.dirname(tensorframes_tpu.__file__),
                "compile_cache": compile_cache.configure_entry_point(),
                "native_packer": native.available(),
                "inherited_tfs_env": sorted(
                    k for k in os.environ if k.startswith("TFS_")
                ),
            }
        ),
        flush=True,
    )
    # phase names on the command line run a subset (debugging on the chip);
    # a subset never exits 0
    phases = [p for p in PHASES if not argv or p[0] in argv]
    ctx: Dict[str, Any] = {"platform": "tpu", "full_width": True}
    return run(phases, Sizes(), ctx, complete=len(phases) == len(PHASES))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
