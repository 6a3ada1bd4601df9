"""Paged KV cache: block-pooled cache buffers + paged attention.

The dense cache (ops/kvcache.py) gives every sequence a full ``max_seq``
stripe of HBM — fine for one-shot ``engine.generate`` batches, wasteful for
a serving pool where sequences have wildly different lengths and shared
prompt prefixes. The paged cache is the TPU-native analogue of
vLLM/PagedAttention:

- ``k``/``v``: [L, NB, bs, Hkv, hd] — a pool of NB fixed-size blocks per
  layer (L = cfg.cache_planes: a looped model, cfg.loop_steps > 1, has a
  plane a (step, layer) pair, step-major). Which blocks a sequence owns
  is *host-side* state, managed by the native C++ allocator
  (native/src/block_pool.cc) with ref-counted radix prefix sharing. A
  one-device pool of fewer K/V heads than a tile has sublanes (whole
  lanes wide, unquantized: trinity-mini's and falcon-h1's 4 x 128)
  stores a position's heads side by side in ONE row,
  [L, NB, bs, 1, Hkv * hd] (heads_in_rows, flat_rows): the same bytes
  in the same order, in (8, 128) tiles of (positions, columns) that a
  wave's scatter and a chunk's gather take where they lie; writers hand
  rows over by heads (fit_rows), readers take the rows as they lie
  (transformer._attend_flat_rows, the paged kernel's flat-rows form) or
  view them by heads (head_rows), and the host arena and the wire keep
  a block by heads (runtime/batcher.py _host_pages). An
  MLA model's pool is latent (cfg.mla_latent_cache):
  ``k`` alone, [L, NB, bs, 1, lane_width(rd + r)], one shared row a
  token a layer (transformer._mla_latent_rows) in the first rd + r
  columns, zeros after them, and no ``v``. A block is a block: the
  allocator, the block tables and the radix cache never look inside.
- ``block_tables``: [R, MB] int32 — per serving *slot*, the block ids
  covering its sequence, in order. Slot count R and max-blocks MB are
  static; XLA sees only fixed shapes.
- ``context_lens``: [R] int32 — tokens currently cached per slot. The
  invariant is position p of a slot's sequence lives in
  ``block_tables[r, p // bs]`` at offset ``p % bs``.

- ``ssm``/``conv``: a second kind of cache in the same (donated) tree,
  for a model with state layers (cfg.ssm, ops/ssm.py): a Mamba-2
  mixer's recurrent state [L, R + 1, H, P, N] float32 and the last
  d_conv - 1 inputs of its convolution [L, R + 1, (d_conv - 1) *
  conv_dim] (stored flat: a minor axis of 3 would be padded to a tile's
  128 lanes), indexed by serving *slot*, not by block: a fixed size a
  request, overwritten on every token, with nothing to share or to cut
  at a block boundary. Row R is the dummy row, which padded wave rows
  write as dead K and V rows write the dummy block. A slot's rows are
  zeroed by the admission program that brings a request's first
  position into it; the radix cache, the host arena and the wire move
  blocks only, so the batcher matches no prefix for such a model
  (runtime/batcher.py).

- ``ring_k``/``ring_v``: a third kind of cache in the same tree, for a
  model whose windowed layers are a kind of their own (cfg.swa,
  MiMo-V2): their K and V, [L_swa, R + 1, ring, 1, Hkv_swa * w], a row a
  serving *slot* as the state planes have (row R the dummy row), and in
  a slot's row position p at ``p % ring``. ring_positions(cfg, bs) is
  the window in whole blocks: a windowed layer's query at or past a
  program's horizon (a tail's first position, a decode chunk's first)
  sees at most window - 1 positions before the horizon, the program's
  own rows ride beside the ring (the fresh tail, the chunk's side
  buffers) and go into it after the stack, in one scatter a plane. Its
  bytes do not grow with max_seq. Row j of a slot whose horizon is h
  holds position h - 1 - ((h - 1 - j) mod ring) (ring_read), which a
  slot's present tenant wrote if it is not negative: a reused slot needs
  no clearing. The block pool of such a model holds its FULL layers'
  K and V alone ([L_full, ...]); a layer's index into either is
  cfg.cache_index. In both a position's heads lie side by side in ONE
  row (flat_rows: [.., 1, Hkv * w], MiMo-V2's 4 x 192 = 768 and 4 x 128
  = 512 columns in the pool, 8 x 192 and 8 x 128 in the ring: whole
  128-lane tiles, nothing padded), as a latent pool's one plane lies: a
  head axis of 4 leaves half of every (8, 128) tile empty and XLA
  re-tiled such a pool around each wave's write (four pool-sized copies
  a program in the described v5e compile), and a minor axis of 192 is no
  whole tile (lane_width). head_rows views a read as heads again. As
  with the state planes, the radix cache, the arena and the wire move
  blocks only, so the batcher matches no prefix for such a model.

Attention over the paged cache gathers each slot's blocks back into a
contiguous [R, MB*bs, ...] view, which ``attend`` then reads once as it
is (grouped-query form, no copy: ops/attention.py). The gather writes that
view to HBM and covers the whole block table whatever the context, so it
is a cost of its own beside attention (``kv_gather`` in PERF.md section
5). ops/pallas/paged_attention.py reads the pages where they lie
instead, each slot as far as its own context: the decode chunks of a
one-device TPU program take it where the pool's shape allows
(models/transformer.py _pool_kernel: mistral-7b, Ouro-2.6B, its latent
plane's rows fetched once as K and V alike, kanana, and flat rows,
falcon-h1's (4 K/V heads of 128: rows of 512) and mimo-v2.5's full
layers' (rows of 768 columns of K and 512 of V) among the benchmark's
cells; PERF.md section 6, PRs 40, 42, 43, 46 and 49), everything else
keeps the gather.

The reference framework has no counterpart at any level — its KV cache was
implicit inside HF ``generate`` (SURVEY.md §2.4).
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from distributed_llm_inferencing_tpu.models.config import ModelConfig
from distributed_llm_inferencing_tpu.ops.attention import attend


LANES = 128   # a TPU tile's minor extent


def lane_width(w: int) -> int:
    """``w`` rounded up to whole 128-lane tiles: the row width a latent
    pool is stored at. The TPU's default layout of an array whose minor
    axis is not a multiple of 128 puts another axis minor-most
    (bf16[7,10241,16,1,576] arrives as {1,4,3,2,0}: the block axis), a
    layout no gather or scatter takes, so every program that touched
    kanana's pool first re-laid the whole of it out, as far as 640
    columns, and copied it back (PERF.md section 6, PR 38). Stored 640
    wide it arrives as {4,2,3,1,0}: blocks of (positions, columns)
    tiles, the singleton head axis out of the way, which the gather by
    (layer, block) and the scatters read and write where it lies."""
    return -(-w // LANES) * LANES


def fit_rows(rows, plane):
    """``rows`` [..., H, w] in ``plane``'s dtype, row form and row width:
    a position's heads side by side where the plane stores them so
    (flat_rows), zeros after a latent pool's rd + r columns
    (lane_width), nothing to do for any other plane."""
    rows = rows.astype(plane.dtype)
    if plane.shape[-2] == 1 and rows.shape[-2] != 1:
        rows = flat_rows(rows)
    pad = plane.shape[-1] - rows.shape[-1]
    if pad == 0:
        return rows
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])


def flat_rows(rows):
    """[..., H, w] -> [..., 1, H * w]: a position's heads side by side
    in one row, as a one-device pool of fewer K/V heads than a tile has
    sublanes and the pool and ring of a model with layer kinds store
    them (init_paged_cache, heads_in_rows)."""
    return rows.reshape(*rows.shape[:-2], 1, -1)


def head_rows(flat, heads: int, w: int):
    """[..., 1, W] (flat_rows, perhaps padded to whole lanes) ->
    [..., heads, w]."""
    return flat[..., 0, :heads * w].reshape(*flat.shape[:-2], heads, w)


def heads_in_rows(cfg: ModelConfig, devices: int = 1) -> bool:
    """Whether ``cfg``'s block pool over ``devices`` devices stores a
    position's K/V heads side by side in ONE row, [L, NB, bs, 1, Hkv *
    w] (flat_rows), and not as an axis, [L, NB, bs, Hkv, w]: the rule,
    read from the pool's shape alone. An (8, 128) tile's second-minor
    axis is the heads' where they are an axis, and fewer than 8 of them
    leave it part empty: XLA stores such planes in (Hkv, 128) tiles and
    re-tiles them whole, there and back, around every write of whole
    blocks and ahead of every gather that attention reads in (8, 128)
    tiles (four copies of a plane an admit program and two a decode
    chunk at trinity-mini's 4 heads, four an admit program at
    falcon-h1's; PERF.md section 6, PRs 38, 45 and 49). With the heads
    side by side the tile's axes are (positions, columns), as a latent
    pool's are, and the wave's scatter and the chunk's gather take the
    planes where they lie. So: an unquantized pool (an int8 pool's
    scale planes have a head axis and no width), no latent one (one
    shared row already), heads of whole lanes (lane_width pads a row,
    not a head), on one device (a mesh shards the head axis:
    parallel/sharding.paged_cache_specs). (A model with layer kinds,
    cfg.swa, keeps flat rows whatever its shapes: init_paged_cache.)"""
    return (devices == 1 and cfg.kv_quant is None
            and not cfg.mla_latent_cache and 1 < cfg.cache_kv_heads < 8
            and cfg.cache_head_dim % LANES == 0)


def flat_pool(cfg: ModelConfig, paged) -> bool:
    """Whether ``paged``'s K and V planes hold flat rows (heads_in_rows;
    the full layers' pool of a model with layer kinds), read from their
    shape: one row a position where the model has several K/V heads and
    no latent pool."""
    return (not cfg.mla_latent_cache and paged.k.shape[3] == 1
            and cfg.num_kv_heads > 1)


class PagedKVCache(NamedTuple):
    k: jax.Array   # [L, NB, bs, Hkv, hd] (model dtype, or int8), or
    #                [L, NB, bs, 1, Hkv * hd] (heads_in_rows)
    v: Optional[jax.Array] = None   # like k; None in a latent pool
    # per-token-per-head scales, present iff cfg.kv_quant == "int8"
    # (ops/kvcache.py quant_kv scheme): [L, NB, bs, Hkv] f32
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    # state layers' per-slot planes (cfg.ssm), no part of planes():
    # [L, R + 1, H, P, N] float32 and [L, R + 1, (d_conv - 1) * conv_dim]
    ssm: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    # windowed layers' per-slot ring (cfg.swa), no part of planes():
    # [L_swa, R + 1, ring, 1, lane_width(Hkv_swa * w)] each
    ring_k: Optional[jax.Array] = None
    ring_v: Optional[jax.Array] = None

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def planes(self) -> tuple:
        """The block pool's arrays, in field order (what a layer scan
        carries; ``with_planes`` puts them back). The per-slot state
        planes are not among them."""
        return tuple(p for p in self[:4] if p is not None)

    def with_planes(self, planes) -> "PagedKVCache":
        """This cache with its block pool's arrays replaced (in
        planes()'s order); the state planes stay as they are."""
        names = [n for n in self._fields[:4] if getattr(self, n) is not None]
        return self._replace(**dict(zip(names, planes)))

    @property
    def bytes_per_token(self) -> int:
        """Pool bytes one cached token takes over all layers."""
        return sum(p.size * p.dtype.itemsize for p in self.planes()) \
            // (self.num_blocks * self.block_size)

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state and conv window one serving slot
        holds over all layers (0 for a model without state layers)."""
        if self.ssm is None:
            return 0
        return sum(p.size * p.dtype.itemsize
                   for p in (self.ssm, self.conv)) // self.ssm.shape[1]

    @property
    def ring_bytes_per_slot(self) -> int:
        """Bytes of windowed layers' K and V one serving slot holds over
        all of them (0 for a model without a ring)."""
        if self.ring_k is None:
            return 0
        return sum(p.size * p.dtype.itemsize
                   for p in (self.ring_k, self.ring_v)) \
            // self.ring_k.shape[1]


RING_MAX = 256   # positions: a wider window is not a slot's to hold


def ring_positions(cfg: ModelConfig, block_size: int) -> int:
    """Positions a slot's ring holds (cfg.swa): the window, in whole
    blocks. A model whose window is wider than RING_MAX is refused: its
    windowed layers want blocks freed behind the window, not a ring."""
    n = -(-cfg.sliding_window // block_size) * block_size
    if n > RING_MAX:
        raise ValueError(
            f"{cfg.name}: a window of {cfg.sliding_window} positions is "
            f"more than a slot's ring holds ({RING_MAX})")
    return n


def ring_read(ring: int, horizon):
    """What a slot's ring holds for a program whose first own position
    is ``horizon`` [R]: (positions [R, ring], valid [R, ring]). Row j
    holds the last position below the horizon that is j mod ring; it is
    the slot's present tenant's if it is not negative. The window mask
    stays the exact cut."""
    j = jnp.arange(ring, dtype=jnp.int32)[None, :]
    last = horizon[:, None] - 1
    pos = last - (last - j) % ring
    return pos, pos >= 0


def ring_take(t: int, tail_len, prefix_len, slots, dummy_row: int,
              ring: int):
    """Which of a wave's fresh tail rows ([B, t, ...]) the ring keeps:
    (index into the tail [B, n], slot row [B, n], offset [B, n]) with
    n = min(t, ring): each wave row's last n real positions, position p
    at p % ring of its slot's row. Entries before a tail's first
    position (a tail shorter than n) go to the dummy row, as a padding
    wave row's all do (its ``slots`` entry is the dummy row already);
    positions past ``tail_len`` in a padded bucket are never taken."""
    n = min(t, ring)
    i = tail_len[:, None] - n + jnp.arange(n, dtype=jnp.int32)[None, :]
    real = i >= 0
    i = jnp.maximum(i, 0)
    return (i, jnp.where(real, slots[:, None], dummy_row),
            (prefix_len[:, None] + i) % ring)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=None, slots: int = 0,
                     devices: int = 1) -> PagedKVCache:
    """The block pool and, for a model with state layers (cfg.ssm), a
    state row and a conv row for each of ``slots`` serving slots and one
    dummy row behind them. ``devices``: how many the pool is laid over
    (the batcher's mesh); heads_in_rows says from it and the pool's
    shape which form the K and V planes take."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.swa is not None:
        # the full layers' pool and the windowed layers' ring
        if cfg.kv_quant is not None:
            raise ValueError("layer kinds keep an unquantized pool and ring")
        n_full = len(cfg.kind_layers("full"))
        n_swa = cfg.num_layers - n_full
        ring = ring_positions(cfg, block_size)

        def planes(lead, heads):   # a position's heads in one row
            return tuple(jnp.zeros(lead + (1, lane_width(heads * w)), dtype)
                         for w in (cfg.head_dim, cfg.v_head_dim_effective))
        k, v = planes((n_full, num_blocks, block_size), cfg.num_kv_heads)
        rk, rv = planes((n_swa, slots + 1, ring), cfg.swa.num_kv_heads)
        return PagedKVCache(k=k, v=v, ring_k=rk, ring_v=rv)
    if cfg.ssm is not None:
        if cfg.kv_quant is not None or cfg.mla_latent_cache:
            raise ValueError("state layers keep an unquantized K and V pool")
        c = cfg.ssm
        pool = init_paged_cache(cfg.replace(ssm=None), num_blocks,
                                block_size, dtype, devices=devices)
        return pool._replace(
            ssm=jnp.zeros((cfg.num_layers, slots + 1, c.n_heads, c.d_head,
                           c.d_state), jnp.float32),
            conv=jnp.zeros((cfg.num_layers, slots + 1, c.conv_elems), dtype))
    shape = (cfg.cache_planes, num_blocks, block_size, cfg.cache_kv_heads,
             cfg.cache_head_dim)
    if cfg.mla_latent_cache:   # config.py refuses kv_quant with it
        return PagedKVCache(k=jnp.zeros(
            shape[:-1] + (lane_width(shape[-1]),), dtype))
    if cfg.kv_quant == "int8":
        return PagedKVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32))
    if cfg.kv_quant is not None:
        raise ValueError(f"unknown kv_quant mode {cfg.kv_quant!r}")
    if heads_in_rows(cfg, devices):
        shape = shape[:-2] + (1, shape[-2] * shape[-1])
    return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def write_token(cache_layer, new, block_tables, positions):
    """Scatter one new token per slot into a layer's block pool.

    cache_layer: [NB, bs, Hkv, hd]; new: [R, Hkv, hd];
    block_tables: [R, MB]; positions: [R] — the position being written.
    """
    bs = cache_layer.shape[1]
    blk = jnp.take_along_axis(
        block_tables, (positions // bs)[:, None], axis=1)[:, 0]   # [R]
    off = positions % bs
    return cache_layer.at[blk, off].set(fit_rows(new, cache_layer))


def write_block_run(cache_layer, new_blocks, block_ids):
    """Scatter runs of whole blocks (prefilled tails) into the pool.

    cache_layer: [NB, bs, Hkv, hd]; new_blocks: [B, T, Hkv, hd] (or
    unbatched [T, Hkv, hd]) with T a multiple of bs; block_ids:
    [B, T // bs] (or [T // bs]). Rows of a batched admission wave scatter
    in one op; duplicate ids may only occur on the reserved dummy block
    (padding rows), where last-write-wins garbage is by design.
    """
    if block_ids.ndim == 1:   # legacy unbatched call: [T, ...] + [T//bs]
        new_blocks, block_ids = new_blocks[None], block_ids[None]
    bs = cache_layer.shape[1]
    b, t = new_blocks.shape[:2]
    reshaped = new_blocks.reshape(b * (t // bs), bs, *new_blocks.shape[2:])
    return cache_layer.at[block_ids.reshape(-1)].set(
        fit_rows(reshaped, cache_layer))


def write_rows(plane, rows, blk, off):
    """Rows of every layer into the stacked plane, in place in a donated
    pool: a program's ONE write of it (the decode chunks' side rows, an
    admission's tails through write_blocks).

    plane: [L, NB, bs, Hkv, w] (a scale plane: no w); rows:
    [L, *blk.shape, Hkv, w]; blk, off: the block id and the offset in it
    of each position. Duplicate positions may only occur on the reserved
    dummy block, where last-write-wins garbage is by design.

    What the scatter moves follows the plane's tile. Where the tile's
    second-minor axis is the heads' it is a position's slab over the
    layers and heads, [L, Hkv, w]. With one head (the latent pool, MQA)
    that axis is the block's positions, and for such a slab XLA re-lays
    the whole pool out to put the layers there, scatters, and copies it
    back (kanana's decode chunk: `copy.922`, `copy.927`, 9.6 ms a chunk
    against 0.43; PERF.md section 6, PR 38): there the layer is an
    index too and the scatter moves rows."""
    rows = fit_rows(rows, plane)
    if plane.shape[3] != 1:
        return plane.at[:, blk, off].set(rows)
    layer = jnp.arange(plane.shape[0]).reshape((-1,) + (1,) * blk.ndim)
    return plane.at[layer, blk, off].set(rows)


def write_blocks(plane, rows, block_ids, first_plane=None):
    """Runs of whole blocks of every layer (a wave's prefilled tails)
    into the stacked plane, in place in a donated pool: one scatter.

    plane: [L, NB, bs, Hkv, w] (a scale plane: no w); rows:
    [L, B, T, Hkv, w] with T a multiple of bs; block_ids: [B, T // bs].
    Duplicate ids may only occur on the reserved dummy block (padding
    rows), as in write_block_run, which writes one layer's plane. With
    ``first_plane`` (a looped model's step, transformer.
    paged_prefill_tail) ``rows`` are that step's layers alone and go to
    planes [first_plane, first_plane + L) of the [T * L, ...] stack.

    The scatter moves whole blocks of (positions, columns) tiles where
    the plane stores a position's heads in one row (heads_in_rows) or
    has 8 heads or more. A head axis of 4 half-fills the tile's
    second-minor axis: XLA re-tiled both planes for this window and
    copied them back, four passes over a plane a wave, and a scatter by
    position (write_rows) in its place halted the core on a v5e
    (PERF.md section 6, PRs 38 and 49): such pools are flat now, or
    sharded over a mesh."""
    L, bs = rows.shape[0], plane.shape[2]
    b, t = rows.shape[1:3]
    ids = block_ids.reshape(-1)
    rows = fit_rows(rows.reshape(L, b * (t // bs), bs, *rows.shape[3:]),
                    plane)
    if first_plane is None:
        return plane.at[:, ids].set(rows)
    # a scatter whose window covers part of the plane axis is expanded
    # by XLA:TPU into a loop of one update a block, over a transposed
    # copy of the rows (0.75 GiB for a wave of 2048 tokens at
    # Ouro-2.6B, described v5e compile): the same loop, written here,
    # takes each block's rows where they lie
    tail = (0,) * (plane.ndim - 2)

    def put(j, plane):
        return jax.lax.dynamic_update_slice(
            plane, jax.lax.dynamic_slice_in_dim(rows, j, 1, axis=1),
            (first_plane, ids[j]) + tail)
    return jax.lax.fori_loop(0, ids.shape[0], put, plane)


def gather_seq(cache_layer, block_tables, layer=None):
    """[NB, bs, Hkv, hd] + [R, MB] -> contiguous [R, MB*bs, Hkv, hd].
    With ``layer`` (a scalar, traced under a layer scan) ``cache_layer``
    is the stacked [L, NB, ...] plane and the gather's index is (layer,
    block): the layer's slice is never taken out of the stack first."""
    g = (cache_layer[block_tables] if layer is None
         else cache_layer[layer, block_tables])   # [R, MB, bs, Hkv, hd]
    r, mb, bs = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape(r, mb * bs, *g.shape[3:])


def kind_scope(kind):
    """Inner named scope of a layer's kind (win | full), or nothing for a
    model of one kind, whose programs then carry the names they had."""
    return jax.named_scope(kind) if kind else contextlib.nullcontext()


def window_columns(window: int, bs: int, mb: int) -> Optional[int]:
    """How many block-table columns hold a window of ``window`` positions
    wherever it starts inside a block: ceil(window / bs) + 1. None where
    that is no fewer than the table's ``mb`` (the whole table is read)."""
    n = -(-window // bs) + 1
    return n if n < mb else None


def window_read(window: int, bs: int, block_tables, horizon):
    """A windowed layer's share of a slot's cached positions: queries at
    or past ``horizon`` [R] see cached positions in (horizon - window,
    horizon) and none before. Returns (block ids [R, n], positions
    [R, n * bs]) of the window_columns that hold them, the first column
    clipped so that all n lie in the table, or None where the whole
    table is read. The window mask stays the exact cut: every position
    left out here had weight zero."""
    r, mb = block_tables.shape
    n = window_columns(window, bs, mb)
    if n is None:
        return None
    first = jnp.clip((horizon - window + 1) // bs, 0, mb - n)
    cols = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    pos = cols[:, :, None] * bs + jnp.arange(bs, dtype=jnp.int32)
    return (jnp.take_along_axis(block_tables, cols, axis=1),
            pos.reshape(r, n * bs))


def paged_attend_decode(q, cache_k_layer, cache_v_layer, block_tables,
                        context_lens,
                        sliding_window: Optional[int] = None,
                        k_scale_layer=None, v_scale_layer=None,
                        alibi=None, softcap: Optional[float] = None, sinks=None,
                        scale: Optional[float] = None):
    """Single-token attention over the paged cache, the plain gather
    formulation.

    q: [R, 1, H, hd]; context_lens: [R] — filled slots INCLUDING the token
    just written (the query sits at context_lens - 1).

    The gather copies MB*bs positions per slot whatever ``context_lens``
    says, and attention then reads all of them. This is
    transformer.paged_decode_step's attention: the reference form of a
    decode pass (tests, benchmarks/chip/compare_reference*.py), which
    writes the pool on every step, and no serving path. The decode
    chunks read the pool in their own way (models/transformer.py
    _pool_kernel: the Pallas paged kernel over K and V planes whose
    heads fill a tile's sublanes or divide them, a latent pool's one
    plane or flat rows, where it was measured
    at 1.5-6.2 times the gather's speed,
    PERF.md section 5; the in-loop gather elsewhere).

    int8 caches (``k_scale_layer``/``v_scale_layer`` present): the
    dequant fuses into the gather/matmul.
    """
    r, mb = block_tables.shape
    bs = cache_k_layer.shape[1]
    with jax.named_scope("kv_gather"):
        # a latent pool's rows are K and V at once, gathered once, and
        # as wide as q_eff in their first columns (lane_width)
        k = gather_seq(cache_k_layer, block_tables)
        if cache_v_layer is cache_k_layer:
            v = k = k[..., :q.shape[-1]]
        else:
            v = gather_seq(cache_v_layer, block_tables)
            if k.shape[-1] != q.shape[-1]:
                # a pool that stores a position's heads in one row
                # (flat_rows)
                k, v = (head_rows(g, g.shape[-1] // q.shape[-1],
                                  q.shape[-1]) for g in (k, v))
        if k_scale_layer is not None:
            from distributed_llm_inferencing_tpu.ops.kvcache import (
                dequant_kv)
            k = dequant_kv(k, gather_seq(k_scale_layer, block_tables),
                           q.dtype)
            v = dequant_kv(v, gather_seq(v_scale_layer, block_tables),
                           q.dtype)
    kv_pos = jnp.broadcast_to(jnp.arange(mb * bs, dtype=jnp.int32),
                              (r, mb * bs))
    kv_valid = kv_pos < context_lens[:, None]
    q_pos = (context_lens - 1)[:, None]
    with jax.named_scope("attention"):
        return attend(q, k, v, q_pos, kv_pos, kv_valid,
                      sliding_window=sliding_window, alibi=alibi,
                      softcap=softcap, sinks=sinks, scale=scale)


def paged_attend_prefix(q, k_new, v_new, cache_k_layer, cache_v_layer,
                        prefix_blocks, prefix_len, q_positions, tail_valid,
                        sliding_window: Optional[int] = None,
                        k_scale_layer=None, v_scale_layer=None,
                        alibi=None, softcap: Optional[float] = None, sinks=None,
                        expand_rows=None, kind: Optional[str] = None,
                        layer=None, scale: Optional[float] = None):
    """Tail-prefill attention: fresh tail K/V plus a cached prefix.

    This is what makes prefix-cache hits save *compute*, not just memory:
    the tail's queries attend the prefix KV gathered straight from shared
    cache blocks — the prefix is never re-run through the model.

    q, k_new, v_new: [B, T, ...] fresh tail projections (B=1 per admission);
    prefix_blocks: [B, PB] block ids covering the cached prefix (dummy-padded);
    prefix_len: [B] — real cached tokens (<= PB*bs);
    q_positions: [B, T] — absolute positions of tail tokens (prefix_len + i);
    tail_valid: [B, T] — tail rows that hold real tokens.

    A latent pool (MLA) passes ``expand_rows``: the gathered prefix rows
    [B, PB*bs, 1, w] -> per-head (K, V), the same expansion that gave
    ``k_new``/``v_new`` from the tail's own rows, so a tail over a cached
    prefix sees the K and V a whole prefill would have computed;
    ``cache_v_layer`` is then None.

    A static ``sliding_window`` shorter than the prefix bucket gathers,
    a row, only the columns that hold the window behind ``prefix_len``
    (window_read: the tail's first query sees the most of the prefix).
    ``kind`` (win | full) names the layer's kind as an inner scope.

    With ``layer`` (gather_seq) the planes and scales are the stacked
    [L, NB, ...] ones and the prefix is gathered by (layer, block): the
    tail is never read back from the pool, so the caller may write it
    after the whole stack (transformer.paged_prefill_tail).

    A pool of flat rows (heads_in_rows) is attended as it lies: ``q``
    comes zero-expanded to a row, ``k_new`` / ``v_new`` flat as the
    gathered prefix is, and ``scale`` is the head's own
    (transformer._flat_rows_q, _own_columns).
    """
    b, t = q.shape[0], q.shape[1]
    bs = cache_k_layer.shape[1 if layer is None else 2]
    read = (window_read(sliding_window, bs, prefix_blocks, prefix_len)
            if isinstance(sliding_window, int) else None)
    if read is not None:
        prefix_blocks, prefix_pos = read
    with jax.named_scope("kv_gather"), kind_scope(kind):
        kp = gather_seq(cache_k_layer, prefix_blocks, layer)  # [B, PB*bs, ..]
        if expand_rows is None:
            vp = gather_seq(cache_v_layer, prefix_blocks, layer)
        if k_scale_layer is not None:   # int8 pool: dequantize the prefix
            from distributed_llm_inferencing_tpu.ops.kvcache import (
                dequant_kv)
            kp = dequant_kv(
                kp, gather_seq(k_scale_layer, prefix_blocks, layer), q.dtype)
            vp = dequant_kv(
                vp, gather_seq(v_scale_layer, prefix_blocks, layer), q.dtype)
    if expand_rows is not None:
        kp, vp = expand_rows(kp)
    elif kp.shape[-2:] != k_new.shape[-2:]:
        # a pool that stores a position's heads in one row (flat_rows),
        # viewed by heads (a model with layer kinds: its K and V rows
        # differ in width; transformer.paged_prefill_tail hands a flat
        # pool's tail over flat, and no view is taken)
        kp = head_rows(kp, *k_new.shape[-2:])
        vp = head_rows(vp, *v_new.shape[-2:])
    if read is None:
        p = prefix_blocks.shape[1] * bs
        prefix_pos = jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (b, p))
    prefix_valid = prefix_pos < prefix_len[:, None]

    with jax.named_scope("attention"), kind_scope(kind):
        return attend(q, (kp, k_new.astype(kp.dtype)),
                      (vp, v_new.astype(vp.dtype)), q_positions,
                      (prefix_pos, q_positions), (prefix_valid, tail_valid),
                      sliding_window=sliding_window, alibi=alibi,
                      softcap=softcap, sinks=sinks, scale=scale)
