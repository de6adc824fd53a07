"""``ops/paged_attention.py`` on the CPU (``interpret=True``) against the
``jnp`` walk it replaces on the chip (``llm/model.py::_walk_pages_jnp``), and
the page count the engine's ``attn_pages`` reads against a count by hand.

The two forms cut the keys into different steps, so their running maxima
differ and with them the probabilities they round: in bfloat16 a probability
carries a relative error of 2**-9, the weighted sum of values of a few
units' size one of about 1e-2, and the result is rounded to bfloat16 again
(2**-9 of up to 4): the tolerance is 3e-2 there, and float32's own 1e-5
where nothing is rounded.  A lane that is not live reads what neither form
specifies and is left out of the comparison.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm import model as M
from fedml_tpu.ops import paged_attention as pa

G, REP, D, PTOK = 2, 4, 128, 4

# lanes as the position of their first query (None: a lane that is not
# live); ``s`` queries a lane; ``tile`` query positions a program
CASES = {
    "tick_full_table": dict(s=1, entries=40, starts=[5, 100, 37]),
    "tick_ring": dict(s=1, entries=12, window=24, ring=True,
                      starts=[5, 100, 37]),
    "tick_window_over_a_full_table": dict(s=1, entries=40, window=24,
                                          starts=[5, 100, 151]),
    "tick_ring_wrapped_more_than_once": dict(s=1, entries=9, window=24,
                                             ring=True, starts=[30, 77, 140]),
    "tick_on_a_pages_first_and_last_token": dict(
        s=1, entries=40, starts=[0, 3, 4, 7, 96, 99]),
    "tick_ring_on_a_pages_first_and_last_token": dict(
        s=1, entries=12, window=24, ring=True, starts=[23, 24, 27, 28, 96, 99]),
    "tick_a_lane_not_live_beside_live_ones": dict(
        s=1, entries=40, starts=[None, 50, None, 9]),
    "tick_lanes_of_very_different_depth": dict(
        s=1, entries=40, starts=[1, 150, 2, 149, 60]),
    "chunk_full_table": dict(s=32, tile=8, entries=40, starts=[64]),
    "chunk_first_of_a_prompt": dict(s=32, tile=8, entries=40, starts=[0]),
    "chunk_ring": dict(s=32, tile=8, entries=16, window=24, ring=True,
                       starts=[64]),
    "chunk_ring_wrapped_more_than_once": dict(
        s=32, tile=16, entries=16, window=24, ring=True, starts=[128]),
    "chunk_window_over_a_full_table": dict(s=32, tile=8, entries=48,
                                           window=24, starts=[96]),
    "chunk_padding_rows": dict(s=32, tile=8, entries=40, starts=[64],
                               prompt=64 + 13),
    "chunk_ring_padding_rows": dict(s=32, tile=8, entries=16, window=24,
                                    ring=True, starts=[64], prompt=64 + 13),
    "chunk_one_tile": dict(s=32, tile=32, entries=40, starts=[40]),
}


def _operands(case, dtype, seed=0):
    """``(q, pool_k, pool_v, tables, pos)`` as the engine would leave them:
    a live lane's table names a page of its own for every block a query of
    the call can see (up to the prompt's last, where the case has padding
    rows: their blocks stay on the trash page), and nothing else."""
    s, entries = case["s"], case["entries"]
    window, ring = case.get("window", 0), case.get("ring", False)
    starts = case["starts"]
    b, pages = len(starts), 400
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (b, G, REP, s, D), dtype)
    pool_k = jax.random.normal(keys[1], (pages, PTOK, G, D), dtype)
    pool_v = jax.random.normal(keys[2], (pages, PTOK, G, D), dtype)
    free = iter(np.random.default_rng(seed).permutation(np.arange(1, pages)))
    tables = np.zeros((b, entries), np.int32)
    pos = np.zeros((b, s), np.int32)
    for i, start in enumerate(starts):
        pos[i] = (start or 0) + np.arange(s)
        if start is None:
            continue
        end = min(case.get("prompt", start + s), start + s) - 1
        first = max(0, start - window + 1) // PTOK if window else 0
        for j in range(first, end // PTOK + 1):
            tables[i, j % entries if ring else j] = next(free)
    return q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(pos)


def _live(case):
    return np.array([start is not None for start in case["starts"]])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(CASES))
def test_kernel_equals_the_jnp_walk(name, dtype):
    case = CASES[name]
    window, ring = case.get("window", 0), case.get("ring", False)
    operands = _operands(case, dtype)
    want = M._walk_pages_jnp(*operands, window=window, ring=ring,
                             sm_scale=D ** -0.5, dtype=dtype)
    # three pages a step: steps that end mid-walk, and a last one part full
    got = pa.paged_attention(*operands, window=window, ring=ring,
                             sm_scale=D ** -0.5, interpret=True,
                             pages_per_step=3, tile=case.get("tile", 0))
    assert got.shape == want.shape and got.dtype == dtype
    live = _live(case)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=tol, rtol=tol)
    # a lane that is not walked reads zero
    assert not np.asarray(got, np.float32)[~live].any()


def _by_hand(case):
    """Blocks that hold a key some query of a (lane, query tile) sees, inside
    what a ring still holds of the lane: counted key by key."""
    s, entries, tile = case["s"], case["entries"], case.get("tile", 0)
    window, ring = case.get("window", 0), case.get("ring", False)
    tile = tile or pa.q_tile(s)
    pages = 0
    for start in case["starts"]:
        if start is None:
            continue
        lane_last = (start + s - 1) // PTOK
        for t0 in range(start, start + s, tile):
            queries = range(t0, t0 + tile)
            blocks = {j // PTOK for j in range(t0 + tile) if any(
                0 <= i - j and (not window or i - j < window)
                for i in queries)}
            pages += sum(not ring or j > lane_last - entries for j in blocks)
    return pages


@pytest.mark.parametrize("name", list(CASES))
def test_page_count_equals_a_count_by_hand(name):
    case = CASES[name]
    _, _, _, tables, pos = _operands(case, jnp.float32)
    kwargs = dict(window=case.get("window", 0), ring=case.get("ring", False),
                  entries=case["entries"], ptok=PTOK)
    live = _live(case)
    got = pa.visited_pages(np.asarray(pos), live, tile=case.get("tile", 0),
                           **kwargs)
    assert got == _by_hand(case) > 0
    # the kernel's prefetched bounds are the same arithmetic, traced
    first, last = jax.jit(lambda p, l: pa.tile_blocks(
        p, l, tile=case.get("tile", 0) or pa.q_tile(case["s"]), **kwargs))(
            pos, jnp.asarray(live, jnp.int32))
    assert int((last - first + 1).sum()) == got
    assert (np.asarray(last - first + 1)[~live] == 0).all()


def test_only_bfloat16_at_whole_lanes_goes_to_the_kernel():
    def operands(dtype=jnp.bfloat16, g=8, rep=16, s=1, d=128, ptok=16):
        shape = jax.ShapeDtypeStruct
        pool = shape((64, ptok, g, d), dtype)
        return (shape((3, g, rep, s, d), dtype), pool, pool,
                shape((3, 20), jnp.int32))

    assert pa.kernel_can_run(*operands())
    assert pa.kernel_can_run(*operands(s=1024))
    assert not pa.kernel_can_run(*operands(jnp.float32))
    assert not pa.kernel_can_run(*operands(d=64))
    assert not pa.kernel_can_run(*operands(g=3))
    assert not pa.kernel_can_run(*operands(rep=4))
    assert not pa.kernel_can_run(*operands(s=40))
    # a program of this process is lowered for the CPU: the walk stays jnp
    assert not pa.engages(*operands())
