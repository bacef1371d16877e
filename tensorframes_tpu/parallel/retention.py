"""The decode step of power retention as a Pallas TPU kernel.

One token a sequence: decay the sequence's state, add ``phi(k) v^T``, read
it out through the group's query heads, write it back —

    S' = g S + phi(k~) v^T      y_i = phi(q~_i)^T S' / (phi(q~_i)^T z' + eps)

(``models/retention.py`` has the mathematics and the order of phi's
entries).  The state is 34 MB a layer a sequence at heads of 128, so the
step is the state's traffic: in plain ``jnp`` XLA makes an update pass
and a read pass, three times the state's bytes where this kernel moves
two — every vector of ``S`` is read once, updated, multiplied into the
group's accumulators while it is in registers, and written where it lay.

Layout it dictates: ``S`` [n_layers, slots, kvh, O, dh (v), dh (a)] and
``z`` [n_layers, slots, kvh, O, dh], float32, by offset ``o`` — at one
offset ``phi(u)[o]`` is ``c[o] * u * roll(u, -o)``, one lane rotation and
two products of a vector, so phi is never stored anywhere: it is made in
registers for the query heads, the key and every offset, from one ``[8,
dh]`` tile a (sequence, KV head) that holds the scaled q's, the scaled k,
v and the gate.  All of it is the vector unit's work; the matrix unit
would round float32 operands or take six passes.

Shape of the kernel: grid ``(slots, kvh)``; a program holds one KV head's
state of one sequence whole, every offset of it (4.26 MB at heads of 128:
one contiguous copy in and one out, double-buffered by the pipeline, which
is why the kernel asks for more than the default scoped VMEM), in and out
aliased, read out of and written into the stacked state of all layers at a
layer index, as ``tfs_paged_attention`` reads the stacked pools.  Measured
alone on a v5e at 20 sequences (my chip runs, PR 37): 2.32 ms a layer
against a least 1.66, 590 GB/s, what a plain elementwise pass reaches;
blocks of a quarter of the value rows (65 strided pieces of 16 KB a copy)
took 3.05 ms however the arithmetic was arranged, and the offsets' loop
left rolled 5.2.  The walk is over the LIVE sequences: ``order`` lists
them first, and past the last the block indices stay where they are, so a
slot that holds no sequence moves no byte of state (Pallas copies a block
only when its index changes) and costs a grid step of nothing.

Off-TPU the kernel runs in Pallas interpret mode (``flash``'s rule and its
one WARNING).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import _resolve_interpret

# the trace reduction and the docs find the kernel by this name
KERNEL_NAME = "tfs_retention_step"

# offsets a trip of the kernel's loop takes, unrolled: rolled one at a
# time the loop was the kernel's bound, not the state's traffic
_GROUP = 5
# what the kernel may ask of VMEM: the state's block four times (in and
# out, each double-buffered) and room for the accumulators' spill
VMEM_LIMIT_BYTES = 40 * 2**20


def pack_rows(g: int) -> int:
    """Rows of the packed tile: the group's q's, k, v, the gate; whole
    sublane tiles."""
    return -(-(g + 3) // 8) * 8


def fits(dh: int, dtype=jnp.float32) -> bool:
    """Heads of one lane tile, in float32: a head's block is then ``(dh / 2
    + 1) dh^2`` values, 4.26 MB, and four of them fit the limit."""
    return dh == 128 and jnp.dtype(dtype) == jnp.float32


def _step_kernel(layer_ref, order_ref, nlive_ref, u_ref, coef_ref, s_in, z_in,
                 s_out, z_out, y_ref, *, g: int, eps: float):
    del layer_ref, order_ref  # the index maps read them
    n = pl.program_id(0)
    O, dh, _ = s_in.shape
    R = u_ref.shape[0]
    group = _GROUP if O % _GROUP == 0 else 1
    zero = jnp.int32(0)

    @pl.when(n < nlive_ref[0])
    def _live():
        U = u_ref[...]  # [R, dh]: q~ of the group, k~, v, the gate
        gate = U[g + 2:g + 3]

        def phi(o):
            """phi at offset ``o`` of every row of the tile at once."""
            return coef_ref[pl.ds(o, 1)] * U * pltpu.roll(U, dh - o, 1)

        # the normaliser, a row an offset, and the group's denominators
        def norm(o, acc):
            p = phi(o)
            z1 = gate * z_in[pl.ds(o, 1)] + p[g:g + 1]
            z_out[pl.ds(o, 1)] = z1
            return acc + p * z1

        acc = jax.lax.fori_loop(
            zero, jnp.int32(O), norm, jnp.zeros((R, dh), jnp.float32)
        )
        den = jnp.sum(acc, axis=1, keepdims=True) + np.float32(eps)

        # v down the sublanes, across the lanes: lane r of the v row,
        # picked out for row r
        row = jax.lax.broadcasted_iota(jnp.int32, (dh, dh), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (dh, dh), 1)
        diag = lane == row
        v = jnp.broadcast_to(
            jnp.sum(jnp.where(diag, U[g + 1:g + 2], 0.0), axis=1, keepdims=True),
            (dh, dh),
        )
        gates = jnp.broadcast_to(gate, (dh, dh))

        def trip(t, accs):
            for r in range(group):
                o = t * group + r
                p = phi(o)
                s1 = gates * s_in[o] + v * p[g:g + 1]
                s_out[o] = s1
                accs = tuple(a + s1 * p[i:i + 1] for i, a in enumerate(accs))
            return accs

        accs = jax.lax.fori_loop(
            zero, jnp.int32(O // group), trip,
            tuple(jnp.zeros((dh, dh), jnp.float32) for _ in range(g)),
        )
        # a head's read-out is a column (a value row a sublane); its place
        # in the output tile is row i, a value a lane
        head = jax.lax.broadcasted_iota(jnp.int32, (R, dh), 0)
        num = jnp.zeros((R, dh), jnp.float32)
        for i, a in enumerate(accs):
            col = jnp.sum(a, axis=1, keepdims=True)
            out = jnp.sum(jnp.where(diag, col, 0.0), axis=0, keepdims=True)
            num = jnp.where(head == i, out, num)
        y_ref[...] = num / den

    @pl.when(nlive_ref[0] == 0)
    def _idle():
        # no sequence at all: the one block the walk rests on goes back
        # as it came
        s_out[...] = s_in[...]
        z_out[...] = z_in[...]


def retention_step(q, k, v, log_g, S, z, live, layer,
                   interpret: Optional[bool] = None):
    """One token a row against ``layer`` of the stacked state.

    q [B, h, dh] (scaled: ``models.retention.scaled``), k [B, kvh, dh]
    (scaled), v [B, kvh, dh], log_g [B, kvh], all float32; S [n_layers, B,
    kvh, O, dh, dh] and z [n_layers, B, kvh, O, dh] float32, of which the
    kernel reads and writes ``layer`` (an int32 scalar, traced or not) of
    the live rows and nothing else; live [B] bool.  Returns ``(y [B, h, dh]
    float32, S', z')``: the state arrays are aliased to the ones passed in,
    and a row that is not live reads zeros."""
    from ..models import retention as ret

    B, h, dh = q.shape
    kvh = k.shape[1]
    g = h // kvh
    O = ret.offsets(dh)
    R = pack_rows(g)
    tile = jnp.concatenate([
        q.reshape(B, kvh, g, dh),
        k[:, :, None],
        v[:, :, None],
        jnp.broadcast_to(jnp.exp(log_g)[..., None, None], (B, kvh, 1, dh)),
        jnp.zeros((B, kvh, R - g - 3, dh), jnp.float32),
    ], axis=2)
    # live rows first, in order; past the last, the last again
    nlive = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    order = jnp.where(
        jnp.arange(B) < nlive, order, order[jnp.maximum(nlive - 1, 0)]
    )

    def head_at(n, hd, nlive_ref):
        """``hd`` while the walk is on a live row, the last head after."""
        return jnp.where(n < nlive_ref[0], hd, kvh - 1)

    def tile_at(n, hd, layer_ref, order_ref, nlive_ref):
        return order_ref[n], head_at(n, hd, nlive_ref), 0, 0

    def s_at(n, hd, layer_ref, order_ref, nlive_ref):
        return layer_ref[0], order_ref[n], head_at(n, hd, nlive_ref), 0, 0, 0

    def z_at(n, hd, layer_ref, order_ref, nlive_ref):
        return layer_ref[0], order_ref[n], head_at(n, hd, nlive_ref), 0, 0

    s_spec = pl.BlockSpec((None, None, None, O, dh, dh), s_at)
    z_spec = pl.BlockSpec((None, None, None, O, dh), z_at)
    y_spec = pl.BlockSpec((None, None, R, dh), tile_at)
    S, z, y = pl.pallas_call(
        functools.partial(_step_kernel, g=g, eps=ret.EPS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, kvh),
            in_specs=[
                y_spec,
                pl.BlockSpec((O, dh), lambda n, hd, *_: (0, 0)),
                s_spec,
                z_spec,
            ],
            out_specs=[s_spec, z_spec, y_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct((B, kvh, R, dh), jnp.float32),
        ],
        # operands count the scalar-prefetch ones: S is the sixth, z the seventh
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=_resolve_interpret(interpret),
        name=KERNEL_NAME,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), order, nlive.reshape(1),
        tile, jnp.asarray(ret.coef(dh)), S, z,
    )
    y = y[:, :, :g].reshape(B, h, dh)
    return jnp.where(live[:, None, None], y, 0.0), S, z
