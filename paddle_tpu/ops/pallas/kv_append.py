"""The decode step's K/V append as one in-place Pallas TPU kernel.

The chip stores a float `[B, H, cap, D]` cache with D = 64 as
`{2,3,1,0:T(8,128)}`: capacity in the lanes, head_dim in the sublanes (a
64-wide minor dimension would waste half of every tile). One appended
row is therefore one lane in each of H x D/8 tiles, and XLA's own write
(`cache_update`'s `vmap` of `dynamic_update_slice`) lowers to a scatter
that XLA:TPU expands into a serial `while` loop of B iterations a cache
tensor, each a handful of launches that move a few KB.

`swapaxes(cache, 2, 3)` is a bitcast of that stored layout (the
`[B, H, D, cap]` row-major view IS what the chip holds), so the kernel
works on the view, aliased in and out: grid step `b` fetches the one
`(H, D, 128)` lane tile column that holds `pos[b]` (scalar-prefetched, so
the block index is data), puts the new row into lane `pos[b] % 128` and
writes the tile back — the smallest whole-tile read-modify-write the
stored layout allows. Blocks no step visits keep the input's bytes
because the output IS the input buffer.

A decode step that attends right after it writes does not come here:
`decode_append_attention` (ops/pallas/decode_attention.py) writes the
rows into the tile its attention fetches anyway. This kernel serves a
write alone (`nn.functional.attention.cache_update`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import _tpu_params

#: lanes of one stored tile: the capacity must be a whole number of them
LANES = 128


def _kv_append_kernel(pos_ref, row_ref, cache_ref, out_ref):
    from jax.experimental import pallas as pl

    lane = pos_ref[pl.program_id(0)] % LANES
    block = cache_ref[...]
    at = jax.lax.broadcasted_iota(jnp.int32, block.shape, block.ndim - 1)
    out_ref[...] = jnp.where(at == lane, row_ref[...], block)


def kv_append(cache, rows, pos, interpret=False):
    """`cache` [B, H, cap, D] with `rows` [B, H, 1, D] written at
    `cache[b, :, pos[b], :]`, in place when the cache is donated. `cap`
    is a multiple of 128; a `pos[b]` outside `[0, cap)` lands where
    `dynamic_update_slice` puts it (a negative one counts from the end,
    then it is clamped: a block index past the capacity would be a DMA
    out of bounds). `interpret=True` runs the Pallas interpreter (CPU
    tests)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, cap, D = cache.shape
    if cap % LANES or rows.shape != (B, H, 1, D):
        raise ValueError(
            f"kv_append: cache {cache.shape} wants a capacity that is a "
            f"multiple of {LANES} and rows [B, H, 1, D], got {rows.shape}")
    pos = jnp.asarray(pos, jnp.int32)
    pos = jnp.clip(jnp.where(pos < 0, pos + cap, pos), 0, cap - 1)
    # slot b's lane tile column that holds pos[b], fetched and written back
    tile = pl.BlockSpec((1, H, D, LANES),
                        lambda b, pos: (b, 0, 0, pos[b] // LANES))
    out = pl.pallas_call(
        _kv_append_kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, D, cap), cache.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, D, 1), lambda b, pos: (b, 0, 0, 0)),
                tile,
            ],
            out_specs=tile,
        ),
        # operand 0 is the prefetched `pos`, 1 the rows, 2 the cache
        input_output_aliases={2: 0},
        compiler_params=_tpu_params("parallel"),
        interpret=interpret,
        name="kv_append",
    )(pos,
      jnp.swapaxes(rows.astype(cache.dtype), 2, 3),
      jnp.swapaxes(cache, 2, 3))
    return jnp.swapaxes(out, 2, 3)
