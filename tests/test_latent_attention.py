"""``ops/latent_attention.py`` on the CPU (``interpret=True``) against the
form it replaces on the chip, ``llm/mla.py::attend_absorbed`` over the
gathered window, and the page count the engine's ``attn_pages`` reads
against a count by hand.

The two forms cut the keys into different steps (the gathered form takes one
softmax over the whole window), so in bfloat16 they round different
probabilities: the tolerances are ``tests/test_paged_attention.py``'s, 3e-2
there and float32's own 1e-5 where nothing is rounded.  A lane that is not
live reads zero from the kernel and a mean over its trash page from the
gathered form: it is left out of the comparison.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm import mla
from fedml_tpu.ops import latent_attention as la
from fedml_tpu.ops import paged_attention as pa

H, RANK, ROPE, NOPE, DV, ROW, PTOK = 4, 128, 32, 16, 24, 256, 4

# lanes as the position of their first query (None: a lane that is not
# live); ``s`` queries a lane; ``tile`` query positions a program
CASES = {
    "tick_ragged_lanes_one_not_live": dict(
        s=1, entries=40, starts=[5, None, 150, 37, 0, 99]),
    "chunk_at_offset_0": dict(s=32, tile=8, entries=40, starts=[0]),
    # 29 pages at three a step: the first tile's last step is part full,
    # and the last query (position 115) ends mid-page
    "chunk_deep_with_a_part_filled_last_page": dict(
        s=32, tile=8, entries=40, starts=[84], prompt=84 + 30),
    "chunk_one_tile": dict(s=32, tile=32, entries=40, starts=[40]),
    "tick_shuffled_pages": dict(s=1, entries=40, starts=[77, 3, 120],
                                shuffled=True),
    "chunk_shuffled_pages": dict(s=32, tile=16, entries=40, starts=[64],
                                 shuffled=True),
}


def _operands(case, dtype, seed=0):
    """``(q_nope, q_rope, pool, tables, w_kvb, pos)`` as the engine would
    leave them: a live lane's table names a page of its own for every block
    a query of the call can see (up to the prompt's last, where the case
    has padding rows: their blocks stay on the trash page), in rising page
    order or shuffled, and nothing else; a pool row is zero past the
    latent."""
    s, entries, starts = case["s"], case["entries"], case["starts"]
    b, pages = len(starts), 300
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q_nope = jax.random.normal(keys[0], (b, H, s, NOPE), dtype)
    q_rope = jax.random.normal(keys[1], (b, H, s, ROPE), dtype)
    pool = jax.random.normal(keys[2], (pages, PTOK, ROW), dtype)
    pool = pool.at[..., RANK + ROPE:].set(0)
    w_kvb = jax.random.normal(keys[3], (RANK, H, NOPE + DV), dtype) \
        * RANK ** -0.5
    free = np.arange(1, pages)
    if case.get("shuffled"):
        free = np.random.default_rng(seed).permutation(free)
    free = iter(free)
    tables = np.zeros((b, entries), np.int32)
    pos = np.zeros((b, s), np.int32)
    for i, start in enumerate(starts):
        pos[i] = (start or 0) + np.arange(s)
        if start is None:
            continue
        end = min(case.get("prompt", start + s), start + s) - 1
        for j in range(end // PTOK + 1):
            tables[i, j] = next(free)
    return q_nope, q_rope, pool, jnp.asarray(tables), w_kvb, jnp.asarray(pos)


def _live(case):
    return np.array([start is not None for start in case["starts"]])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(CASES))
def test_kernel_equals_the_absorbed_form_over_the_gathered_window(name, dtype):
    case = CASES[name]
    q_nope, q_rope, pool, tables, w_kvb, pos = _operands(case, dtype)
    scale = (NOPE + ROPE) ** -0.5
    window = pool[tables].reshape(len(tables), -1, ROW)
    want = mla.attend_absorbed(q_nope, q_rope, window, w_kvb, pos, scale,
                               (RANK, NOPE))
    # three pages a step: steps that end mid-walk, and a last one part full
    q = mla.absorb_query(q_nope, q_rope, w_kvb[..., :NOPE], ROW, dtype)
    o_lat = la.latent_attention(q, pool, tables, pos, rank=RANK,
                                sm_scale=scale, interpret=True,
                                pages_per_step=3, tile=case.get("tile", 0))
    assert o_lat.shape == q.shape[:-1] + (RANK,) and o_lat.dtype == dtype
    got = mla.unabsorb(o_lat, w_kvb[..., NOPE:])
    live = _live(case)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=tol, rtol=tol)
    # a lane that is not walked reads zero
    assert not np.asarray(o_lat, np.float32)[~live].any()
    # the entry ``_paged_attend`` takes on the chip is these three in a row
    whole = mla.attend_pool(q_nope, q_rope, pool, tables, w_kvb, pos, scale,
                            (RANK, NOPE), interpret=True)
    assert whole.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(whole, np.float32)[live],
        np.asarray(want, np.float32)[live], atol=tol, rtol=tol)


def _by_hand(case):
    """Blocks that hold a key some query of a (lane, query tile) sees,
    counted key by key."""
    s, tile = case["s"], case.get("tile", 0) or pa.q_tile(case["s"])
    pages = 0
    for start in case["starts"]:
        if start is None:
            continue
        for t0 in range(start, start + s, tile):
            pages += len({j // PTOK for j in range(t0 + tile)
                          if any(j <= i for i in range(t0, t0 + tile))})
    return pages


@pytest.mark.parametrize("name", list(CASES))
def test_page_count_equals_a_count_by_hand(name):
    case = CASES[name]
    pos = np.stack([(start or 0) + np.arange(case["s"])
                    for start in case["starts"]])
    got = pa.visited_pages(pos, _live(case), window=0, ring=False,
                           entries=case["entries"], ptok=PTOK,
                           tile=case.get("tile", 0))
    assert got == _by_hand(case) > 0


def test_only_bfloat16_at_whole_lanes_goes_to_the_kernel():
    def operands(dtype=jnp.bfloat16, h=64, s=1, row=640, ptok=16, rank=512):
        shape = jax.ShapeDtypeStruct
        return (shape((3, h, s, row), dtype), shape((64, ptok, row), dtype),
                shape((3, 20), jnp.int32), rank)

    assert la.kernel_can_run(*operands())
    assert la.kernel_can_run(*operands(s=512))
    assert not la.kernel_can_run(*operands(jnp.float32))
    assert not la.kernel_can_run(*operands(row=576))
    assert not la.kernel_can_run(*operands(rank=448))
    assert not la.kernel_can_run(*operands(ptok=4))
    assert not la.kernel_can_run(*operands(h=8))
    assert not la.kernel_can_run(*operands(s=40))
    # a program of this process is lowered for the CPU: the read stays jnp
    assert not la.engages(*operands())
