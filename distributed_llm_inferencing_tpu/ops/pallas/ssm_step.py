"""Pallas TPU kernel: a Mamba-2 mixer's one-step state update over the
state plane, in place (a decode pass of a model with state layers,
ops/ssm.py mix_step).

A slot's recurrent state is [H, P, N] float32 a layer (4 MiB at
Falcon-H1-34B's 32 x 128 x 256), and a decode pass has to read and write
every live slot's once: with 64 slots 3.2 of a pass's 11.4 GB. XLA
splits the update into two fusions, one that reads the state for
``y = S_t C`` and one that reads it again and writes ``S_t``, so the
state crosses HBM three times a layer (PERF.md section 6, PR 41). This
kernel takes a (slot, group) tile [H / G, P, N] of the stacked plane
``[L, R + 1, H, P, N]`` where it lies, at the layer's index, computes

    S_t = decay S_{t-1} + (dt x) (outer) B        y = S_t C

a head at a time and writes the tile back through the aliased output:
one read, one write. A row that is not alive comes with decay 1 and
dt x = 0, which leaves its state bit for bit; the dummy row behind the
slots and the other layers' rows are never brought in.

Everything a head needs beside its state arrives so that Mosaic
broadcasts it natively: ``decay`` as scalars (SMEM), ``dt x`` with P on
the sublanes ([.., P, heads]: a head's column broadcasts along the
lanes), B and C as rows of N lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_llm_inferencing_tpu.utils.profiler import pallas_call_site

F32 = jnp.float32


def supported(n_heads: int, n_groups: int, d_head: int, d_state: int,
              dtype) -> bool:
    """Whether the kernel takes this state plane: float32, a head's
    [P, N] in whole (8, 128) tiles, and a group's tile (the pipeline
    holds four: two in, two out) inside the VMEM the call asks for."""
    k = n_heads // n_groups
    return (jnp.dtype(dtype) == F32 and d_head % 8 == 0
            and d_state % 128 == 0 and k * d_head * d_state * 4 <= 4 * 2 ** 20)


def _kernel(li_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref, out_ref, y_ref,
            *, heads: int):
    del li_ref                       # the index maps' alone
    i = pl.program_id(0)
    b = b_ref[0]                     # [1, N]
    c = c_ref[0]
    for k in range(heads):           # static: a head's [P, N] at a time
        s = decay_ref[i, k] * s_ref[0, 0, 0, k] \
            + dtx_ref[0, :, k:k + 1] * b
        out_ref[0, 0, 0, k] = s
        y_ref[0, :, k:k + 1] = jnp.sum(s * c, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_step(plane, layer, decay, dtx, b, c, interpret: bool = False):
    """plane [L, R + 1, H, P, N] float32 (donated: updated in place);
    layer: the layer's index (a traced scalar under a layer scan); decay
    [R, H], dtx [R, H, P] (= dt x), b and c [R, G, N], all float32. Rows
    0..R-1 of ``plane[layer]`` are slots 0..R-1's. Returns (the plane
    with those rows advanced, y [R, H, P] = S_t C)."""
    n_layers, rows1, h, p, n = plane.shape
    r, g = b.shape[:2]
    k = h // g
    tiles = plane.reshape(n_layers, rows1, g, k, p, n)
    # a (slot, group) a grid step; a head's dt x as a column of P sublanes
    dtx_t = jnp.swapaxes(dtx.reshape(r * g, k, p), 1, 2)        # [RG, P, K]
    tile = pl.BlockSpec((1, 1, 1, k, p, n),
                        lambda i, li: (li[0], i // g, i % g, 0, 0, 0))
    row = pl.BlockSpec((1, 1, n), lambda i, li: (i, 0, 0))
    col = pl.BlockSpec((1, p, k), lambda i, li: (i, 0, 0))
    pallas_call_site()   # utils/profiler.py: counted as traced
    out, y = pl.pallas_call(
        functools.partial(_kernel, heads=k),
        out_shape=(jax.ShapeDtypeStruct(tiles.shape, F32),
                   jax.ShapeDtypeStruct((r * g, p, k), F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(r * g,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), col, row, row,
                      tile],
            out_specs=(tile, col)),
        # operand 5 (after the prefetched index): the plane's tiles
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * k * p * n * 4 + 8 * 2 ** 20),
        interpret=interpret, name="ssm_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      decay.reshape(r * g, k).astype(F32), dtx_t.astype(F32),
      b.reshape(r * g, 1, n).astype(F32), c.reshape(r * g, 1, n).astype(F32),
      tiles)
    return (out.reshape(plane.shape),
            jnp.swapaxes(y, 1, 2).reshape(r, h, p))
