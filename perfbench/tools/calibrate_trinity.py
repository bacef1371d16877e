"""Readings that the limits of `trinity_large_l5_ep8.decode_longmix`'s
`correct` are set from, in one process: for each seed the cell's set-up, a
window at the cell's own load, and the numbers `correct` compares over the
requests the window finished; for the first seed also

* the program with its decode step's attention in bfloat16: the compared
  requests teacher-forced again through the decode step with the kernel off
  (`kv_pager.paged_kernel_fits` False, so `transformer._cache_attention`
  attends) and that attention's scores and softmax in bfloat16
  (`bf16_cache_attention`), and, to show the path alone moves nothing, with
  the kernel off and the attention as it is;
* what the reference puts in the program's place reads on the compared
  request with the longest prompt, in int8 (the control) and with each of
  its mutations (`refs/trinity_decoder.VARIANTS`: attending past the window,
  the full layer rotated, no output gate, picks without the selection bias,
  attention in bfloat16), beside what the program reads on that request.

    python3 perfbench/tools/calibrate_trinity.py --seeds 1,2 --seconds 20

One JSON line per seed; not part of a benchmark run.
"""

import argparse
import contextlib
import gc
import importlib
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "trinity_large_l5_ep8.decode_longmix"


def bf16_cache_attention(q, ck, cv, positions_q, window=0, k_positions=None):
    """`transformer._cache_attention` with its scores and its softmax in
    bfloat16, where the configuration's file says float32."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    B, L, h, dh = q.shape
    S, kvh = ck.shape[1], ck.shape[2]
    qg = q.astype(bf).reshape(B, L, kvh, h // kvh, dh)
    s = jnp.einsum("blkgd,bskd->bkgls", qg, ck.astype(bf), preferred_element_type=bf)
    s = s * bf(1.0 / np.sqrt(dh))
    k_pos = jnp.arange(S, dtype=jnp.int32)[None, None, None, None, :]
    if k_positions is not None:
        k_pos = k_positions[:, None, None, None, :]
    q_pos = positions_q[:, None, None, :, None]
    mask = q_pos >= k_pos
    if window:
        mask = mask & (q_pos - k_pos < window)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    att = jnp.einsum("bkgls,bskd->blkgd", p, cv.astype(bf), preferred_element_type=jnp.float32)
    return att.astype(q.dtype).reshape(B, L, h, dh)


@contextlib.contextmanager
def decode_attention(low):
    """The decode step off the kernel, its attention in bfloat16 if `low`."""
    from tensorframes_tpu.models import kv_pager
    from tensorframes_tpu.models import transformer as tfm

    fits, sound = kv_pager.paged_kernel_fits, tfm._cache_attention
    kv_pager.paged_kernel_fits = lambda *a, **k: False
    if low:
        tfm._cache_attention = bf16_cache_attention
    try:
        yield
    finally:
        kv_pager.paged_kernel_fits, tfm._cache_attention = fits, sound


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    from perfbench import run, work
    from perfbench.refs import trinity_decoder
    from tensorframes_tpu import compile_cache

    compile_cache.configure(os.path.join(ROOT, ".cache", "jax"))
    _, cell, config, traffic = run.load_cell(CELL, args.rehearse)
    peak = None if args.rehearse else work.peaks(jax.devices()[0].device_kind)
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = run.context(cell, config, traffic, seed, peak)
        driver = importlib.import_module("perfbench.drivers." + traffic["driver"]).Driver(ctx)
        try:
            driver.setup()
            obs = driver.window(args.seconds)
        finally:
            driver.release()
        line = {"seed": seed, "program": driver.check(), "failed": obs["failed"],
                "attempted": obs["attempted"], "tokens_per_s": obs["tokens_per_s"],
                "compared": [len(driver.requests[i]["prompt"]) for i, _ in driver.checked]}
        if n == 0:
            served = driver.program
            for name, low in (("program_gather", False), ("program_bf16_attention", True)):
                with decode_attention(low):
                    driver.program = driver.teacher_forced()
                line[name] = driver.judged(driver.gaps())
            driver.program = served
            longest = max(driver.checked, key=lambda c: len(driver.requests[c[0]]["prompt"]))
            driver.checked = [longest]
            line["program_longest"] = driver.judged(driver.gaps())
            line["control"] = driver.control()
            for variant in trinity_decoder.VARIANTS:
                line[variant] = driver.control(variant)
        print(json.dumps(line), flush=True)
        del driver
        gc.collect()  # the last seed's weights go before the next set-up


if __name__ == "__main__":
    main()
