"""Attention over the latent page pool through block tables: the paged read
of ``llm/mla.py`` in the absorbed form, as one Pallas TPU kernel.

``q`` (b, h, s, row) — a query row is ``[q_nope · W_UK ; q_rope ; zeros]``,
as wide as a pool row — at positions ``pos`` (b, s) attends to the pages
``tables`` (b, entries) names in ``pool`` (pages, P, row), whose row is
``[c_kv (rank) ; k_r ; zeros]``: **the row is the key and its first ``rank``
numbers are the value**, for every head alike.  Entry ``e`` of a table
stands for block ``e`` of the sequence; a key at position j is visible to
the query at i iff ``j <= i``.  A lane whose table is all zeros (not live)
is not walked and reads zero.  The result is ``o_lat`` (b, h, s, rank);
``W_UV`` is applied outside.

- :func:`latent_attention` — the kernel.  The pool stays in HBM and is only
  read.  The grid is (lane, query tile); a program visits **its own blocks
  only**, from the first to the last a query of the tile can see
  (``ops/paged_attention.py::tile_blocks``, scalar-prefetched with the
  tables), several pages a step by asynchronous copies into one half of a
  double buffer while the other half is computed on.  One fetch of a page
  serves key and value.  The rows of a program are all ``h`` heads of its
  query positions: the heads of one lane in a tick, the heads of a tile of
  positions in a chunk.  The arithmetic is ``mla.attend_absorbed``'s: float32
  scores from the operands' dtype, times the scale, a float32 running
  maximum and sum, probabilities cast to the operands' dtype for the
  product with the value, float32 accumulation, one division at the end.
  A step wholly at or before every query of the tile skips the mask; steps
  past every query are not visited at all.
- :func:`kernel_can_run` — whether ``MLA._paged_attend`` sends its read here
  when the program is lowered for a TPU: bfloat16 ``q`` and pool, a row and
  a value of whole lanes, pages and query rows that tile.
- :func:`engages` — the same for the programs this process lowers for its
  own devices: what the engine's ``attn_pages`` counts by
  (``ops/paged_attention.py::visited_pages``).

It shares no body with ``ops/paged_attention.py``: that one parts pairs of
kv heads out of two pools; this one has one pool, no head axis and 64 query
heads against one row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .paged_attention import LANES, _NEG, _lanes, q_tile, tile_blocks

#: pages a step fetches and computes on at once (twice that in VMEM, for
#: the double buffer): of a tick, whose steps are short, and of a chunk.
#: PERF.md section 6 has the sweep
PAGES_PER_STEP_TICK = 32
PAGES_PER_STEP_CHUNK = 16


def kernel_can_run(q, pool, tables, rank: int) -> bool:
    """Whether :func:`latent_attention` takes these operands as the chip's
    compiler wants them: bfloat16 throughout (the one dtype lowered, timed
    and compared on the chip), a row and a value of whole lanes, a page of
    whole bf16 tiles, and query rows that tile (the heads of a tick, the
    query tile of a chunk)."""
    _, h, s, row = q.shape
    rows = h if s == 1 else q_tile(s)
    return (q.dtype == pool.dtype == jnp.bfloat16
            and tables.dtype == jnp.int32 and pool.shape[2] == row
            and row % LANES == 0 and rank % LANES == 0 and 0 < rank <= row
            and pool.shape[1] % 16 == 0 and rows % 16 == 0
            and s % q_tile(s) == 0)


def engages(q, pool, tables, rank: int) -> bool:
    """Whether a read of these operands is the kernel in the programs this
    process lowers for its own devices: what ``platform_dependent`` picks
    there, for the engine's ``attn_pages`` counter."""
    return (jax.default_backend() == "tpu"
            and kernel_can_run(q, pool, tables, rank))


def _vmem_limit(npg: int, ptok: int, row: int, rank: int, rows: int,
                itemsize: int) -> int:
    """The VMEM a call may take: a third over what it holds (the double
    buffer of pages, the queries and the result twice each for the
    pipeline, the float32 accumulator and the two terms of its update, the
    running maximum and sum and the rows' positions, a step's scores,
    probabilities and mask: within a fifth of the least the chip's compiler
    takes at the cell's shapes), and no more: what a call does not claim
    the compiler uses for what surrounds it (PERF.md section 5)."""
    keys = npg * ptok
    held = (2 * keys * row * itemsize + 2 * rows * (row + rank) * itemsize
            + 12 * rows * (rank + LANES) + 14 * rows * keys)
    return (held * 4 // 3 + (2 << 20)) >> 20 << 20


def _kernel(tabs, first, last, q_ref, pos_ref, pool_hbm, o_ref, buf, sems,
            m_ref, l_ref, acc_ref, *, entries: int, sm_scale: float,
            npg: int):
    """One (lane, query tile): its blocks, ``npg`` pages a step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    groups, tq, row = q_ref.shape
    rows, rank = groups * tq, o_ref.shape[-1]
    ptok = buf.shape[2]
    keys = npg * ptok
    lane, tile = pl.program_id(0), pl.program_id(1)
    at = lane * pl.num_programs(1) + tile
    lo, hi = first[at], last[at]
    steps = (hi - lo + npg) // npg

    @pl.when((lane == 0) & (tile == 0))
    def _():
        # what a step does not fetch it masks, and a masked key's product
        # with the value has to be zero: the buffer never holds what is no
        # number
        buf[...] = jnp.zeros_like(buf)

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def pages(i):
        """Pages step ``i`` holds: all of a step but the last's."""
        return jnp.minimum(npg, hi + 1 - lo - i * npg)

    def copy(slot, p, page):
        return pltpu.make_async_copy(pool_hbm.at[page], buf.at[slot, p],
                                     sems.at[slot])

    def fetch(i, slot):
        def one(p, carry):
            copy(slot, p, tabs[lane * entries + lo + i * npg + p]).start()
            return carry
        jax.lax.fori_loop(0, pages(i), one, 0)

    def wait(i, slot):
        def one(p, carry):
            copy(slot, 0, 0).wait()
            return carry
        jax.lax.fori_loop(0, pages(i), one, 0)

    q = q_ref[...].reshape(rows, row)
    # the queries' positions, a column; the rows of a tile repeat them once
    # a head
    qpos = pos_ref[...]
    if groups > 1:
        qpos = jnp.concatenate([qpos] * groups, axis=0)
    qlo = jnp.min(qpos)

    def attend(slot, k0, masked: bool):
        """The step in ``slot``, whose first key stands at ``k0``."""
        page = buf.at[slot].reshape(keys, row)
        scores = jax.lax.dot_general(
            q, page[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            seen = qpos >= k0 + jax.lax.broadcasted_iota(
                jnp.int32, (rows, keys), 1)
            scores = jnp.where(seen, scores, _NEG)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(scores - _lanes(m_new, keys))
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, rank) + jnp.dot(
            p.astype(q.dtype), page[:, :rank],
            preferred_element_type=jnp.float32)

    @pl.when(steps > 0)
    def _():
        fetch(0, 0)

    def step(i, carry):
        slot = i % 2

        @pl.when(i + 1 < steps)
        def _():
            fetch(i + 1, 1 - slot)

        wait(i, slot)
        k0 = (lo + i * npg) * ptok
        # every key fetched, at or before every query: nothing for a mask
        # to cut
        whole = (pages(i) == npg) & (k0 + keys - 1 <= qlo)

        @pl.when(whole)
        def _():
            attend(slot, k0, False)

        @pl.when(jnp.logical_not(whole))
        def _():
            attend(slot, k0, True)

        return carry

    jax.lax.fori_loop(0, steps, step, 0)
    out = acc_ref[...] / _lanes(jnp.maximum(l_ref[...], 1e-30), rank)
    o_ref[...] = out.astype(o_ref.dtype).reshape(groups, tq, rank)


@functools.partial(jax.jit, static_argnames=(
    "rank", "sm_scale", "interpret", "pages_per_step", "tile"))
def latent_attention(q, pool, tables, pos, *, rank: int, sm_scale: float,
                     interpret: bool = False, pages_per_step: int = 0,
                     tile: int = 0):
    """Attention of ``q`` (b, h, s, row) at ``pos`` (b, s) over the pages
    ``tables`` (b, entries) names in ``pool`` (pages, P, row) -> (b, h, s,
    rank) in ``q``'s dtype: the module's docstring is the contract.
    ``pages_per_step`` and ``tile`` (0: the module's) are for the sweep of
    ``tools/tpu_paged_attn_bench.py``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, row = q.shape
    ptok, entries = pool.shape[1], tables.shape[1]
    npg = min(pages_per_step or (PAGES_PER_STEP_TICK if s == 1
                                 else PAGES_PER_STEP_CHUNK), entries)
    tile = tile or q_tile(s)
    pos = pos.astype(jnp.int32)
    live = jnp.any(tables != 0, axis=1).astype(jnp.int32)
    first, last = tile_blocks(pos, live, window=0, ring=False,
                              entries=entries, ptok=ptok, tile=tile)
    if s == 1:      # a tick: the lane's h heads are one query tile
        groups, tq, rowpos = 1, h, jnp.broadcast_to(pos, (b, h))
        q = q.reshape(b, 1, h, row)
    else:
        groups, tq, rowpos = h, tile, pos
    rows = groups * tq

    def q_map(lane, t, *_):
        return lane, 0, t, 0

    out = pl.pallas_call(
        functools.partial(_kernel, entries=entries, sm_scale=sm_scale,
                          npg=npg),
        out_shape=jax.ShapeDtypeStruct(q.shape[:-1] + (rank,), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((None, groups, tq, row), q_map),
                      pl.BlockSpec((None, tq, 1),
                                   lambda lane, t, *_: (lane, t, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, groups, tq, rank), q_map),
            grid=(b, rowpos.shape[1] // tq),
            scratch_shapes=[
                pltpu.VMEM((2, npg, ptok, row), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, LANES), jnp.float32),
                pltpu.VMEM((rows, LANES), jnp.float32),
                pltpu.VMEM((rows, rank), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                npg, ptok, row, rank, rows, q.dtype.itemsize)),
        interpret=interpret,
        name="latent_attention",
    )(tables.reshape(-1), first.reshape(-1), last.reshape(-1), q,
      rowpos[:, :, None], pool)
    return out.reshape(b, h, s, rank)


__all__ = ["latent_attention", "kernel_can_run", "engages"]
