"""The decode step of a Mamba-2 mixer's state as a Pallas TPU kernel.

One token a sequence: decay each head's state, add the token, read it out —

    S' = exp(dt A) S + (dt x) B^T        y = S' C + D x

per head, ``S`` in ``R^{P x N}`` (head dim by ``d_state``: 128 x 256, 128 KB
in float32), ``B`` and ``C`` shared by the heads of a group
(``models/ssm.py`` has the mathematics).  At Falcon-H1's widths a
sequence's state is 4 MB a layer, so the step is the state's traffic: this
kernel reads every head's state once, updates it, multiplies it into the
read-out while it is in registers and writes it back where it lay — two
passes of the state's bytes, where ``jnp`` makes XLA write the update and
read it again for the read-out.

Layout it reads: ``S`` [n_layers, slots, heads, P, N] float32, of which it
reads and writes ``layer`` (a scalar, traced or not) in place, aliased, as
``tfs_retention_step`` reads its stacked state.  A program holds one GROUP
of one sequence: its ``J`` heads' states (2 MB at 16 heads), the group's
``B`` and ``C`` as two rows of an ``[8, N]`` tile, the heads' inputs as
columns of a ``[P, J]`` tile (x) and rows of an ``[8, J]`` tile (exp(dt A),
dt, D), and it writes the heads' read-outs as the columns of a ``[P, J]``
tile.  A head's column is picked out of a tile by a select and a lane sum,
and put into the output tile the same way: the vector unit's work, a few
vectors a head against the state's 32.

The walk is over the LIVE sequences: ``order`` lists them first, and past
the last the block indices stay where they are, so a slot that holds no
sequence moves no byte of state (Pallas copies a block only when its index
changes) and costs a grid step of nothing.

Off-TPU the kernel runs in Pallas interpret mode (``flash``'s rule and its
one WARNING).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import _resolve_interpret

# the trace reduction and the docs find the kernel by this name
KERNEL_NAME = "tfs_ssm_step"
# what the kernel may ask of VMEM: the state's block four times (in and
# out, each double-buffered) and the small tiles
VMEM_LIMIT_BYTES = 32 * 2**20


def block_bytes(heads_per_group: int, head_dim: int, d_state: int) -> int:
    """One program's block of state: a group's heads, float32."""
    return 4 * heads_per_group * head_dim * d_state


def fits(heads_per_group: int, head_dim: int, d_state: int,
         dtype=jnp.float32) -> bool:
    """A float32 state whose head is whole sublane tiles by whole lane
    tiles, and whose group's block, four times over, fits the limit with a
    quarter to spare.  What it refuses takes ``models.ssm.step``."""
    return (
        jnp.dtype(dtype) == jnp.float32
        and head_dim % 8 == 0
        and d_state % 128 == 0
        and 4 * block_bytes(heads_per_group, head_dim, d_state)
        <= VMEM_LIMIT_BYTES * 3 // 4
    )


def _step_kernel(layer_ref, order_ref, nlive_ref, x_ref, sc_ref, bc_ref,
                 s_in, s_out, y_ref):
    del layer_ref, order_ref  # the index maps read them
    n = pl.program_id(0)
    J = s_in.shape[0]

    @pl.when(n < nlive_ref[0])
    def _live():
        X = x_ref[...]  # [P, J]: a head's x a column
        SC = sc_ref[...]  # [8, J]: exp(dt A), dt, D, a head a lane
        b, c = bc_ref[0:1, :], bc_ref[1:2, :]  # [1, N]
        lane_x = jax.lax.broadcasted_iota(jnp.int32, X.shape, 1)
        lane_s = jax.lax.broadcasted_iota(jnp.int32, SC.shape, 1)
        Y = jnp.zeros(X.shape, jnp.float32)
        for j in range(J):
            x = jnp.sum(jnp.where(lane_x == j, X, 0.0), axis=1, keepdims=True)
            s = jnp.sum(jnp.where(lane_s == j, SC, 0.0), axis=1, keepdims=True)
            decay, dt, d = s[0:1], s[1:2], s[2:3]  # [1, 1] each
            s1 = decay * s_in[j] + (dt * x) * b  # [P, N]
            s_out[j] = s1
            y = jnp.sum(s1 * c, axis=1, keepdims=True) + d * x  # [P, 1]
            Y = jnp.where(lane_x == j, y, Y)
        y_ref[...] = Y

    @pl.when(nlive_ref[0] == 0)
    def _idle():
        # no sequence at all: the one block the walk rests on goes back
        # as it came
        s_out[...] = s_in[...]


def ssm_step(x, B, C, dt, A, D, S, live, layer,
             interpret: Optional[bool] = None):
    """One token a row against ``layer`` of the stacked state.

    x [R, H, P], B and C [R, G, N], dt [R, H] (after softplus), A [H] and D
    [H], all float32; S [n_layers, R, H, P, N] float32, of which the kernel
    reads and writes ``layer`` of the live rows and nothing else; live [R]
    bool.  Returns ``(y [R, H, P] float32, S')``, y with D's skip: ``S'`` is
    aliased to the ``S`` passed in, and a row that is not live reads
    zeros."""
    R, H, P = x.shape
    G, N = B.shape[1:]
    J = H // G
    xcols = x.reshape(R, G, J, P).transpose(0, 1, 3, 2)  # [R, G, P, J]
    scal = jnp.stack(
        [jnp.exp(dt * A), dt, jnp.broadcast_to(D, dt.shape)], axis=1
    ).reshape(R, 3, G, J).transpose(0, 2, 1, 3)  # [R, G, 3, J]
    scal = jnp.pad(scal, ((0, 0), (0, 0), (0, 5), (0, 0)))
    rows = jnp.pad(
        jnp.stack([B, C], axis=2), ((0, 0), (0, 0), (0, 6), (0, 0))
    )  # [R, G, 8, N]
    # live rows first, in order; past the last, the last again
    nlive = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    order = jnp.where(
        jnp.arange(R) < nlive, order, order[jnp.maximum(nlive - 1, 0)]
    )

    def group_at(n, g, nlive_ref):
        """``g`` while the walk is on a live row, the last group after."""
        return jnp.where(n < nlive_ref[0], g, G - 1)

    def tile_at(n, g, layer_ref, order_ref, nlive_ref):
        return order_ref[n], group_at(n, g, nlive_ref), 0, 0

    def s_at(n, g, layer_ref, order_ref, nlive_ref):
        return layer_ref[0], order_ref[n], group_at(n, g, nlive_ref), 0, 0

    s_spec = pl.BlockSpec((None, None, J, P, N), s_at)
    col_spec = pl.BlockSpec((None, None, P, J), tile_at)
    S, y = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, G),
            in_specs=[
                col_spec,
                pl.BlockSpec((None, None, 8, J), tile_at),
                pl.BlockSpec((None, None, 8, N), tile_at),
                s_spec,
            ],
            out_specs=[s_spec, col_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct((R, G, P, J), jnp.float32),
        ],
        # operands count the scalar-prefetch ones: S is the seventh
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=_resolve_interpret(interpret),
        name=KERNEL_NAME,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), order, nlive.reshape(1),
        xcols, scal, rows, S,
    )
    y = y.transpose(0, 1, 3, 2).reshape(R, H, P)
    return jnp.where(live[:, None, None], y, 0.0), S
