"""The readings that the limit of `ssm_state_gap` has to refuse: `calibrate.py`'s
runs of a Falcon-H1 cell with the mixer's state kept in bfloat16, the nearest
precision below the float32 the configuration states.

    python3 perfbench/tools/calibrate_falcon_h1_state.py \
        --workload falcon_h1_34b_l4.decode_crowd --seeds 1,2 --seconds 20

Both serving executables are wrapped so that the state they return is rounded:
the values a program that stored the state in that type would hold after every
update, prefill and decode step alike.  The arguments are `calibrate.py`'s; not
part of a benchmark run.
"""

import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


@contextlib.contextmanager
def rounded_state(dtype):
    import jax
    import jax.numpy as jnp
    from tensorframes_tpu.models import kv_pager

    # `reduce_precision`, not a cast down and up: XLA is free to drop that pair
    # (`xla_allow_excess_precision`), and on the chip it does.  In place: two
    # copies of the state do not fit beside the weights and the pages.  The
    # tail is in the compute dtype already; the float32 state is rounded
    kind = jnp.finfo(dtype)
    rounded = jax.jit(lambda st: (jax.lax.reduce_precision(st[0], kind.nexp, kind.nmant),
                                  st[1]), donate_argnums=0)
    sound = {name: getattr(kv_pager, name) for name in ("paged_decode_step", "paged_prefill")}

    def wrap(fn):
        def wrapped(*args, **kw):
            tokens, kp, vp, state, stats = fn(*args, **kw)
            return tokens, kp, vp, rounded(state), stats
        return wrapped

    for name, fn in sound.items():
        setattr(kv_pager, name, wrap(fn))
    try:
        yield
    finally:
        for name, fn in sound.items():
            setattr(kv_pager, name, fn)


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import calibrate

    with rounded_state("bfloat16"):
        calibrate.main()


if __name__ == "__main__":
    main()
