"""In-place KV row writer for the decode step's stacked cache.

Each decode step appends one K and one V row per sequence to a cache laid
out ``(layers, B, Hkv, S, D)``.  Written as an XLA scatter, the update
takes a position-major layout that the decode kernel cannot read, and
XLA relayouts (copies) the whole cache every layer.  This kernel aliases
the cache buffers (``input_output_aliases``) and rewrites only the one
``(tile, D)`` tile per KV head that holds the new row: the grid runs over
the batch, each program reads and writes back ``Hkv`` tiles of K and of
V, and everything else in the cache stays where it is.  The layer index
and the per-row slots are scalar-prefetched, so the tile's address is
known before the program runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows per rewritten tile: the sublane count of a TPU memory tile
TILE_ROWS = 8


def _write_kernel(
    slot_ref, layer_ref, k_row_ref, v_row_ref, k_in, v_in, k_out, v_out,
    *, tile,
):
    del layer_ref  # used by the index maps only
    row = slot_ref[pl.program_id(0)] % tile
    for src, old, out in ((k_row_ref, k_in, k_out), (v_row_ref, v_in, v_out)):
        tile_vals = old[0, 0].astype(jnp.float32)            # (Hkv, tile, D)
        hit = jax.lax.broadcasted_iota(jnp.int32, tile_vals.shape, 1) == row
        new = jnp.where(hit, src[0].astype(jnp.float32), tile_vals)
        out[0, 0] = new.astype(out.dtype)


def kv_row_write(
    k_cache: jax.Array,   # (L, B, Hkv, S, D)
    v_cache: jax.Array,   # (L, B, Hkv, S, D)
    k_row: jax.Array,     # (B, Hkv, D), the cache's dtype
    v_row: jax.Array,     # (B, Hkv, D)
    slot: jax.Array,      # (B,) int32 cache position of each row's new entry
    layer: jax.Array,     # () int32 layer of the stack to write
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Write ``k_row[b]`` / ``v_row[b]`` at ``[layer, b, :, slot[b]]``,
    in place: the returned caches alias ``k_cache`` and ``v_cache``."""
    L, B, Hkv, S, D = k_cache.shape
    # a cache whose length no tile divides ends in a partial tile: its rows
    # past the end are read as unspecified values and never written back
    tile = min(TILE_ROWS, S)

    def tile_map(b, slot_ref, layer_ref):
        return (layer_ref[0], b, 0, slot_ref[b] // tile, 0)

    def row_map(b, slot_ref, layer_ref):
        return (b, 0, 0, 0)

    cache_spec = pl.BlockSpec((1, 1, Hkv, tile, D), tile_map)
    row_spec = pl.BlockSpec((1, Hkv, 1, D), row_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row_spec, row_spec, cache_spec, cache_spec],
        out_specs=(cache_spec, cache_spec),
    )
    return pl.pallas_call(
        functools.partial(_write_kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ),
        # operands: slot, layer, k_row, v_row, k_cache, v_cache
        input_output_aliases={4: 0, 5: 1},
        name="kv_row_write",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        slot.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        k_row[:, :, None].astype(k_cache.dtype),
        v_row[:, :, None].astype(v_cache.dtype),
        k_cache,
        v_cache,
    )
