"""Attention over a page pool through block tables: the walk form of the
paged read (``llm/model.py::_walk_pages``) as one Pallas TPU kernel.

``q`` (b, h_kv, rep, s, d) at positions ``pos`` (b, s) attends to the pages
``tables`` (b, entries) names in ``pool_k``/``pool_v`` (pages, P, h_kv, d).
Entry ``e`` of a table stands for block ``e`` of the sequence, or, in a
``ring``, for the one block ``j`` in ``(last - entries, last]`` with
``j % entries == e``, ``last`` the block of the lane's highest position in
the call.  A key at position j is visible to the query at i iff
``0 <= i - j`` (``< window``, where the layer has one).  A lane whose table
is all zeros (not live) is not walked and reads zero.

- :func:`paged_attention` — the kernel.  The pools stay in HBM and are only
  read.  The grid is (lane, query tile); a program visits **its own blocks
  only**, from the first a query of the tile can see to the last
  (:func:`tile_blocks`, scalar-prefetched with the tables), several pages a
  step: whole pages (all kv heads, contiguous) come by asynchronous copies
  into one half of a double buffer while the other half is computed on,
  and the heads are split in VMEM (two bf16 heads share a 32-bit sublane: a
  strided load of the pair, a shift and a mask).  The arithmetic is the
  ``jnp`` walk's: float32 scores from the operands' dtype, a float32
  running maximum and sum, probabilities cast to the operands' dtype for
  the product with V, float32 accumulation, one division at the end.  A
  step wholly inside every query's sight skips the mask; steps outside any
  query's sight are not visited at all.
- :func:`tile_blocks`, :func:`visited_pages` — the blocks a program visits
  and their count over a call, one arithmetic for the kernel's prefetched
  bounds (traced) and the engine's ``attn_pages`` counter (host integers).
- :func:`kernel_can_run` — whether ``_walk_pages`` sends a read here when
  the program is lowered for a TPU: bfloat16 ``q`` and pools, whole lanes of
  ``head_dim``, pages and query rows that tile.

One body serves the decode tick (``s`` 1: ``rep`` query rows a kv head and
lane, bound by the pages' bytes) and the prefill chunk (``s`` a multiple of
the query tile: ``rep × tile`` rows a kv head, bound by the matrix unit).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
#: pages a step fetches and computes on at once (K and V each; twice that
#: in VMEM, for the double buffer): of a tick, whose steps are short, and of
#: a chunk.  PERF.md section 6 has the sweep
PAGES_PER_STEP_TICK = 32
PAGES_PER_STEP_CHUNK = 16
#: query positions a program of a chunk holds (``rep`` rows each)
Q_TILE = 32
_NEG = -1e30


def q_tile(s: int) -> int:
    """Query positions a program holds of a call with ``s`` a lane."""
    return min(Q_TILE, s)


def tile_blocks(pos, live, *, window: int, ring: bool, entries: int,
                ptok: int, tile: int):
    """``(first, last)`` (b, s // tile): the blocks the program of each
    (lane, query tile) visits, ``first .. last`` inclusive (none where
    ``last < first``: a lane that is not ``live``).  ``pos`` (b, s) and
    ``live`` (b,) are numpy or jax arrays; the arithmetic is the same."""
    b, s = pos.shape
    tiles = pos.reshape(b, s // tile, tile)
    lo, hi = tiles.min(axis=-1), tiles.max(axis=-1)
    last = hi // ptok
    behind = lo - window + 1
    first = (behind * (behind > 0)) // ptok if window else last * 0
    if ring:    # a ring holds the lane's last ``entries`` blocks and no more
        short = last.max(axis=1, keepdims=True) - entries + 1 - first
        first = first + short * (short > 0)
    return first, last - (last - first + 1) * (1 - live[:, None])


def visited_pages(pos, live, *, window: int, ring: bool, entries: int,
                  ptok: int, tile: int = 0) -> int:
    """Pages the kernel fetches (K and V of a page count once) for one call
    of one layer: host arithmetic on numpy arrays."""
    pos = np.asarray(pos)
    first, last = tile_blocks(pos, np.asarray(live).astype(pos.dtype),
                              window=window, ring=ring, entries=entries,
                              ptok=ptok, tile=tile or q_tile(pos.shape[1]))
    return int((last - first + 1).sum())


def kernel_can_run(q, pool_k, pool_v, tables) -> bool:
    """Whether :func:`paged_attention` takes these operands as the chip's
    compiler wants them: bfloat16 throughout (the one dtype lowered, timed
    and compared on the chip), ``head_dim`` whole lanes, an even count of kv
    heads (two share a 32-bit row) whose page is whole bf16 tiles, and query
    rows that tile (``rep`` of a tick, the query tile of a chunk)."""
    _, g, rep, s, d = q.shape
    ptok = pool_k.shape[1]
    rows = rep if s == 1 else q_tile(s)
    return (all(a.dtype == jnp.bfloat16 for a in (q, pool_k, pool_v))
            and tables.dtype == jnp.int32
            and pool_k.shape == pool_v.shape and pool_k.shape[2:] == (g, d)
            and d % LANES == 0 and g % 2 == 0 and (ptok * g) % 16 == 0
            and rows % 16 == 0 and s % q_tile(s) == 0)


def engages(q, pool_k, pool_v, tables) -> bool:
    """Whether a walk over these operands is the kernel in the programs this
    process lowers for its own devices: what ``platform_dependent`` picks
    there, for the engine's ``attn_pages`` counter."""
    return (jax.default_backend() == "tpu"
            and kernel_can_run(q, pool_k, pool_v, tables))


def _vmem_limit(npg: int, ptok: int, g: int, d: int, rows: int,
                itemsize: int) -> int:
    """The VMEM a call may take: a third over what it holds (the double
    buffer of pages, the queries and the result twice each for the
    pipeline, the float32 accumulator, running maximum and sum, a step's
    scores, probabilities and mask), and no more: what a call does not
    claim the compiler uses for what surrounds it (PERF.md section 5)."""
    keys = npg * ptok
    held = (4 * keys * g * d * itemsize + 4 * g * rows * d * itemsize
            + 4 * g * rows * (d + 2 * LANES) + 14 * rows * keys)
    return (held * 4 // 3 + (2 << 20)) >> 20 << 20


def _lanes(x, n: int):
    """``x`` (rows, LANES), every lane of a row alike, as (rows, n)."""
    if n == LANES:
        return x
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _kernel(tabs, first, last, efirst, q_ref, pos_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *, window: int,
            ring: bool, entries: int, sm_scale: float, npg: int):
    """One (lane, query tile): its blocks, ``npg`` pages a step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, groups, tq, d = q_ref.shape
    rows = groups * tq
    ptok = kbuf.shape[2]
    keys = npg * ptok
    lane, tile = pl.program_id(0), pl.program_id(1)
    at = lane * pl.num_programs(1) + tile
    lo, hi, e_lo = first[at], last[at], efirst[at]
    steps = (hi - lo + npg) // npg

    @pl.when((lane == 0) & (tile == 0))
    def _():
        # what a step does not fetch it masks, and a masked key's product
        # with V has to be zero: the buffers never hold what is no number
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def pages(i):
        """Pages step ``i`` holds: all of a step but the last's."""
        return jnp.minimum(npg, hi + 1 - lo - i * npg)

    def copies(slot, p, page):
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, p],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, p],
                                      sems.at[1, slot]))

    def fetch(i, slot):
        def one(p, carry):
            e = e_lo + i * npg + p
            if ring:    # the walk is shorter than the ring: one wrap at most
                e = jnp.where(e >= entries, e - entries, e)
            for copy in copies(slot, p, tabs[lane * entries + e]):
                copy.start()
            return carry
        jax.lax.fori_loop(0, pages(i), one, 0)

    def wait(i, slot):
        def one(p, carry):
            for copy in copies(slot, 0, 0):
                copy.wait()
            return carry
        jax.lax.fori_loop(0, pages(i), one, 0)

    # the queries' positions, a column; the rows of a tile repeat them
    # ``groups`` times over
    qpos = pos_ref[...]
    if groups > 1:
        qpos = jnp.concatenate([qpos] * groups, axis=0)
    qlo, qhi = jnp.min(qpos), jnp.max(qpos)

    def attend(slot, k0, masked: bool):
        """The step in ``slot``, whose first key stands at ``k0``."""
        if masked:
            ahead = qpos - (k0 + jax.lax.broadcasted_iota(
                jnp.int32, (rows, keys), 1))
            seen = ahead >= 0
            if window:
                seen &= ahead < window
        # (keys, g, d) as rows of d: token-major, heads within, so head h is
        # every g-th row.  Two bf16 heads share a 32-bit row: the pair is
        # loaded as words and parted by a shift and a mask
        kflat = kbuf.at[slot].reshape(keys * g, d)
        vflat = vbuf.at[slot].reshape(keys * g, d)

        def heads(flat, first_head):
            if flat.dtype.itemsize == 4:
                return [flat[pl.ds(first_head, keys, stride=g), :]]
            both = flat.bitcast(jnp.uint32)[
                pl.ds(first_head // 2, keys, stride=g // 2), :]
            return [pltpu.bitcast(half, jnp.float32).astype(flat.dtype)
                    for half in (both << 16, both & jnp.uint32(0xFFFF0000))]

        together = 1 if kflat.dtype.itemsize == 4 else 2
        for h0 in range(0, g, together):
            for h, (k, v) in enumerate(zip(heads(kflat, h0),
                                           heads(vflat, h0)), h0):
                qh = q_ref[h].reshape(rows, d)
                scores = jax.lax.dot_general(
                    qh, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if masked:
                    scores = jnp.where(seen, scores, _NEG)
                m_old = m_ref[h]
                m_new = jnp.maximum(
                    m_old, jnp.max(scores, axis=1, keepdims=True))
                alpha = jnp.exp(m_old - m_new)
                # a step wholly outside a row's sight leaves its running
                # maximum at -1e30; the row's own key, in a later step,
                # wipes what is counted here (alpha = 0)
                p = jnp.exp(scores - _lanes(m_new, keys))
                m_ref[h] = m_new
                l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
                acc_ref[h] = acc_ref[h] * _lanes(alpha, d) + jnp.dot(
                    p.astype(q_ref.dtype), v,
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
        # every key fetched, at or before every query and inside every
        # query's window: nothing for a mask to cut
        whole = (pages(i) == npg) & (k0 + keys - 1 <= qlo)
        if window:
            whole &= k0 > qhi - window

        @pl.when(whole)
        def _():
            attend(slot, k0, False)

        @pl.when(jnp.logical_not(whole))
        def _():
            attend(slot, k0, True)

        return carry

    jax.lax.fori_loop(0, steps, step, 0)
    for h in range(g):
        out = acc_ref[h] / _lanes(jnp.maximum(l_ref[h], 1e-30), d)
        o_ref[h] = out.astype(o_ref.dtype).reshape(groups, tq, d)


@functools.partial(jax.jit, static_argnames=(
    "window", "ring", "sm_scale", "interpret", "pages_per_step", "tile"))
def paged_attention(q, pool_k, pool_v, tables, pos, *, window: int,
                    ring: bool, sm_scale: float, interpret: bool = False,
                    pages_per_step: int = 0, tile: int = 0):
    """Attention of ``q`` (b, h_kv, rep, s, d) at ``pos`` (b, s) over the
    pages ``tables`` (b, entries) names in ``pool_k``/``pool_v`` (pages, P,
    h_kv, d), in ``q``'s dtype: the module's docstring is the contract.
    ``pages_per_step`` and ``tile`` (0: the module's) are for the sweep of
    ``tools/tpu_paged_attn_bench.py``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, g, rep, s, d = q.shape
    ptok, entries = pool_k.shape[1], tables.shape[1]
    npg = min(pages_per_step or (PAGES_PER_STEP_TICK if s == 1
                                 else PAGES_PER_STEP_CHUNK), entries)
    tile = tile or q_tile(s)
    pos = pos.astype(jnp.int32)
    live = jnp.any(tables != 0, axis=1).astype(jnp.int32)
    first, last = tile_blocks(pos, live, window=window, ring=ring,
                              entries=entries, ptok=ptok, tile=tile)
    efirst = first % entries if ring else first
    if s == 1:      # a tick: the kv head's rep rows are one query tile
        groups, tq, rowpos = 1, rep, jnp.broadcast_to(pos, (b, rep))
        q = q.reshape(b, g, 1, rep, d)
    else:
        groups, tq, rowpos = rep, tile, pos
    tiles = rowpos.shape[1] // tq
    rows = groups * tq

    def q_map(lane, t, *_):
        return lane, 0, 0, t, 0

    out = pl.pallas_call(
        functools.partial(_kernel, window=window, ring=ring, entries=entries,
                          sm_scale=sm_scale, npg=npg),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((None, g, groups, tq, d), q_map),
                      pl.BlockSpec((None, tq, 1),
                                   lambda lane, t, *_: (lane, t, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, g, groups, tq, d), q_map),
            grid=(b, tiles),
            scratch_shapes=[
                pltpu.VMEM((2, npg, ptok, g, d), pool_k.dtype),
                pltpu.VMEM((2, npg, ptok, g, d), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((g, rows, LANES), jnp.float32),
                pltpu.VMEM((g, rows, LANES), jnp.float32),
                pltpu.VMEM((g, rows, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                npg, ptok, g, d, rows, q.dtype.itemsize)),
        interpret=interpret,
        name="paged_attention",
    )(tables.reshape(-1), first.reshape(-1), last.reshape(-1),
      efirst.reshape(-1), q, rowpos[:, :, None], pool_k, pool_v)
    return out.reshape(b, g, rep, s, d)


__all__ = ["paged_attention", "kernel_can_run", "engages", "tile_blocks",
           "visited_pages", "q_tile"]
