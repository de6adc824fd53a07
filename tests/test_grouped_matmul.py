"""``ops/grouped_matmul.py`` on the CPU (``interpret=True``): the kernels
against ``jax.lax.ragged_dot`` on the rows of the held groups — what lies
past them is unspecified and never compared — and ``expert_ffn`` through the
kernels against ``expert_ffn`` through ``ragged_dot``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm import moe
from fedml_tpu.ops import grouped_matmul as gm

TM = gm.ROW_TILE

# (rows, sizes of the held groups): what the kernels must get right
CASES = {
    "even": (256, [64, 64, 64, 64]),
    "one_expert_takes_every_row": (256, [0, 256, 0, 0]),
    "some_experts_empty": (256, [0, 70, 0, 0, 31, 0, 9]),
    "no_row_held": (256, [0, 0, 0, 0]),
    "held_rows_end_mid_tile": (384, [100, 40, 33]),
    "rows_no_multiple_of_the_tile": (300, [10, 0, 150, 7]),
    "fewer_rows_than_a_tile": (40, [3, 5, 0, 7]),
    "a_group_over_three_tiles": (512, [120, 270, 5]),
}


def _operands(m, sizes, dtype, k=256, n=128, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    rows = jax.random.normal(keys[0], (m, k), dtype)
    w = jax.random.normal(keys[1], (len(sizes), k, n), dtype) * k ** -0.5
    return rows, w, jnp.asarray(sizes, jnp.int32)


def _tol(dtype):
    return 1e-5 if dtype == jnp.float32 else 2e-2


def _by_hand(sizes, m):
    """(group, row tile) pairs in which a group has rows."""
    tm, start, pairs = gm.row_tile(m), 0, 0
    for size in sizes:
        if size:
            pairs += (start + size - 1) // tm - start // tm + 1
        start += size
    return pairs


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_grouped_matmul_equals_ragged_dot_on_the_held_rows(case, dtype):
    m, sizes = CASES[case]
    rows, w, sz = _operands(m, sizes, dtype)
    want = jax.lax.ragged_dot(rows, w, sz, preferred_element_type=jnp.float32)
    got = gm.grouped_matmul(rows, w, sz, interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    held = sum(sizes)
    np.testing.assert_allclose(np.asarray(got[:held]), np.asarray(want[:held]),
                               atol=_tol(dtype), rtol=_tol(dtype))
    # the counter's definition: every (group, row tile) pair with rows in
    # it, which is sum(ceil(size / tm)) unless a group straddles a tile's end
    visited = int(gm.visited_tiles(sz, m))
    assert visited == _by_hand(sizes, m)
    least = sum(-(-s // gm.row_tile(m)) for s in sizes)
    assert least <= visited <= least + sum(s > 0 for s in sizes)


def test_aligned_groups_visit_ceil_size_over_tile_each():
    sizes = [TM, 0, 3 * TM, TM]
    assert int(gm.visited_tiles(jnp.asarray(sizes, jnp.int32), 8 * TM)) \
        == sum(-(-s // TM) for s in sizes) == 5


def test_a_narrower_column_tile_computes_the_same(monkeypatch):
    m, sizes = CASES["rows_no_multiple_of_the_tile"]
    rows, w, sz = _operands(m, sizes, jnp.float32, k=256, n=512)
    assert gm._col_tile(256, 512, 4, 1) == 512
    want = gm.grouped_matmul(rows, w, sz, interpret=True)
    monkeypatch.setattr(gm, "WEIGHT_TILE_BYTES", 256 * 128 * 4)
    assert gm._col_tile(256, 512, 4, 1) == 128        # four column tiles
    got = gm.grouped_matmul(rows, w, sz, interpret=True)
    held = sum(sizes)
    np.testing.assert_array_equal(np.asarray(got[:held]), np.asarray(want[:held]))


def test_tiles_come_from_the_shapes():
    # the latent cell's: two 1.8 MB tiles for gate and up, one of 4 MB down
    assert gm._col_tile(7168, 2048, 2, 2) == 128
    assert gm._col_tile(2048, 7168, 2, 1) == 1024
    assert gm._col_tile(128, 128, 4, 1) == 128
    assert gm.row_tile(512) == gm.row_tile(4096) == TM and gm.row_tile(40) == 48
    bf, f32 = jnp.bfloat16, jnp.float32
    ok = jnp.zeros((4, 256, 128), bf)
    assert gm.kernel_can_run(jnp.zeros((8, 256), bf), ok)
    assert not gm.kernel_can_run(jnp.zeros((8, 256), f32), ok)       # two dtypes
    # float32 was never read on the chip: the dispatch keeps it on ragged_dot
    assert not gm.kernel_can_run(jnp.zeros((8, 256), f32),
                                 jnp.zeros((4, 256, 128), f32))
    assert not gm.kernel_can_run(jnp.zeros((8, 64), bf), jnp.zeros((4, 64, 128), bf))
    assert not gm.kernel_can_run(jnp.zeros((8, 128), bf), jnp.zeros((4, 128, 192), bf))
    assert not gm.kernel_can_run(jnp.zeros((8, 256), jnp.float16),
                                 jnp.zeros((4, 256, 128), jnp.float16))
    # a contraction too long for a grid step to hold whole: ragged_dot's
    assert not gm.kernel_can_run(jnp.zeros((8, 1 << 16), bf),
                                 jax.ShapeDtypeStruct((4, 1 << 16, 128), bf))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gated_matmul_equals_silu_dot_times_dot(dtype):
    m, sizes = CASES["some_experts_empty"]
    rows, w_gate, sz = _operands(m, sizes, dtype)
    w_up = _operands(m, sizes, dtype, seed=1)[1]
    dot = lambda w: jax.lax.ragged_dot(rows, w, sz,
                                       preferred_element_type=jnp.float32)
    want = (jax.nn.silu(dot(w_gate)) * dot(w_up)).astype(dtype)
    got = gm.gated_matmul(rows, w_gate, w_up, sz, interpret=True)
    assert got.dtype == dtype
    held = sum(sizes)
    np.testing.assert_allclose(
        np.asarray(got[:held], np.float32), np.asarray(want[:held], np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


# -- expert_ffn through the kernels against expert_ffn through ragged_dot -------

def _through_the_kernels(*operands, limit=0.0):
    return (gm.swiglu_pallas(*operands, True, limit),
            gm.visited_tiles(operands[-1], operands[0].shape[0]))


def _through_ragged_dot(*operands, limit=0.0):
    return gm.swiglu_ragged(*operands, limit), jnp.zeros((), jnp.int32)


def _layer(dtype, n=48, k=4, e=8, d=128, f=256, held=(2, 5), one_expert=False):
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(keys[0], (n, d), dtype)
    gates = jax.nn.softmax(jax.random.normal(keys[1], (n, k)), -1)
    experts = jnp.argsort(jax.random.uniform(keys[2], (n, e)), -1)[:, :k]
    if one_expert:
        experts = jnp.full((n, k), held[0] + 1)
    w = [jax.random.normal(key, shape, dtype) * shape[1] ** -0.5
         for key, shape in zip(keys[3:], ((held[1], d, f), (held[1], d, f),
                                          (held[1], f, d)))]
    return x, gates, experts, w, held[0]


@pytest.mark.parametrize("one_expert", [False, True],
                         ids=["spread", "every_pair_on_one_expert"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_expert_ffn_kernel_path_equals_ragged_path(monkeypatch, dtype, one_expert):
    x, gates, experts, w, first = _layer(dtype, one_expert=one_expert)
    monkeypatch.setattr(moe, "grouped_swiglu", _through_ragged_dot)
    want, sizes, tiles = moe.expert_ffn(x, gates, experts, *w, first)
    assert int(tiles) == 0
    monkeypatch.setattr(moe, "grouped_swiglu", _through_the_kernels)
    got, sizes_k, tiles = moe.expert_ffn(x, gates, experts, *w, first)
    assert sizes_k.tolist() == sizes.tolist()
    assert int(tiles) == _by_hand(sizes.tolist(), x.shape[0] * gates.shape[1]) > 0
    if one_expert:                      # no pair dropped at any skew
        assert sizes.tolist() == [0, x.shape[0] * gates.shape[1], 0, 0, 0]
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_expert_ffn_gradients_agree(monkeypatch):
    x, gates, experts, w, first = _layer(jnp.float32)

    def loss(x, gates, *w):
        return (moe.expert_ffn(x, gates, experts, *w, first)[0] ** 2).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    monkeypatch.setattr(moe, "grouped_swiglu", _through_ragged_dot)
    want = grad(x, gates, *w)
    monkeypatch.setattr(moe, "grouped_swiglu", _through_the_kernels)
    got = jax.jit(grad)(x, gates, *w)
    for g, r in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)


def test_the_dispatch_takes_ragged_dot_off_the_tpu():
    """``swiglu`` chooses by the platform the program is lowered for: a CPU
    program holds no kernel and counts no tile — bfloat16 at widths the
    kernels would take on a TPU, float32 (``ragged_dot``'s everywhere) and
    widths that are no whole lane tiles."""
    bf, f32 = jnp.bfloat16, jnp.float32
    for dtype, d, f, kernels_on_tpu in ((bf, 128, 256, True),
                                        (f32, 128, 256, False),
                                        (bf, 24, 40, False)):
        x, _, _, w, _ = _layer(dtype, d=d, f=f)
        assert gm.kernel_can_run(x, *w[:2]) == kernels_on_tpu
        sizes = jnp.asarray([9, 0, 20, 4, 15], jnp.int32)
        fn = jax.jit(gm.swiglu)
        assert "pallas" not in fn.lower(x, *w, sizes).as_text()
        y, tiles = fn(x, *w, sizes)
        assert int(tiles) == 0
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(jax.jit(gm.swiglu_ragged)(x, *w, sizes)))
