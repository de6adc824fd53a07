"""Grouped matmuls for the expert layer (``llm/moe.py::expert_ffn``).

The rows arrive sorted by group (expert); ``sizes`` (count,) says how many
each of the ``count`` held groups got, and whatever lies past their sum
belongs to no group held here.  For those held rows the functions compute
what ``jax.lax.ragged_dot(rows, w, sizes, preferred_element_type=float32)``
computes — operands in the model's dtype, float32 accumulation:

- :func:`swiglu_ragged` — the held experts' SwiGLU as three ``ragged_dot``s.
  Runs on any backend, differentiable by XLA; the semantic reference.
- :func:`grouped_matmul`, :func:`gated_matmul` — Pallas TPU kernels built
  for few rows against many weight bytes (a decode tick: a dozen rows an
  expert against 29 MB a matrix).  The grid walks (group, row tile) visits
  that a scalar-prefetched table names, so a group no row chose is never
  read and row tiles past the last held row are neither computed nor
  written: **what lies in those rows of the result is unspecified** (select
  them away, never multiply).  A grid step holds the whole contraction (on
  the chip it beat every split of it by 5-25%, PERF.md section 6), so a
  group whose rows straddle two row tiles keeps its weight tile on chip for
  both visits: every weight byte of a hit group crosses HBM once a call.
  :func:`gated_matmul` reads the rows once for gate and up and writes
  ``silu(gate) * up`` (float32) in the rows' dtype, so neither float32
  product reaches HBM.
- :func:`swiglu_pallas` — the two kernels chained, with a ``custom_vjp``
  whose backward is :func:`swiglu_ragged`'s own VJP on the saved operands.
- :func:`swiglu` — what ``expert_ffn`` calls: the kernels where the program
  is lowered for a TPU and the shapes allow, ``ragged_dot`` elsewhere, and
  beside the result the row tiles the kernels visited (0 on ``ragged_dot``).
  Only bfloat16 goes to the kernels (:func:`kernel_can_run`): the one dtype
  lowered, timed and compared on the chip.

Group sizes are traced: one compiled program serves every routing.  A model
whose SwiGLU is clamped (``swiglu_limit``) passes ``limit``, a static option of
every function here: ``silu(min(gate, limit)) * clip(up, -limit, limit)``
(:func:`swiglu_act`), inside the gate-and-up kernel as in ``ragged_dot``'s
form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANES = 128
#: rows a grid step multiplies: the MXU's height.  A shorter tile costs the
#: same passes (the weights' load bounds them), a taller one more
ROW_TILE = 128
#: bytes of weight tiles a grid step holds (all of them, one buffer; the
#: pipeline keeps two of each in flight), and the VMEM a call may take.
#: Small on purpose: alone the kernels are as fast with twice the tile, but
#: what a call does not take the compiler uses to keep the sorted rows and
#: the result of a prefill chunk (58 and 117 MB) on chip around it
WEIGHT_TILE_BYTES = 4 << 20
VMEM_LIMIT_BYTES = 16 << 20


def swiglu_act(gate, up, limit: float = 0.0):
    """``silu(gate) * up``; where ``limit`` > 0 the gate clamped to at most
    ``limit`` and the up projection to ``±limit`` first."""
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def swiglu_ragged(rows, w_gate, w_up, w_down, sizes, limit: float = 0.0):
    """``(silu(rows @ gate_g) * (rows @ up_g)) @ down_g`` for every row of
    held group g (the operands of the product clamped where ``limit`` > 0:
    :func:`swiglu_act`); float32.  Rows past the held groups read zero."""
    dot = lambda a, w: jax.lax.ragged_dot(
        a, w, sizes, preferred_element_type=jnp.float32)
    act = swiglu_act(dot(rows, w_gate), dot(rows, w_up), limit).astype(rows.dtype)
    return dot(act, w_down)


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def row_tile(m: int) -> int:
    """The row tile for ``m`` sorted rows."""
    return min(ROW_TILE, _round_up(m, 16))


def _col_tile(k: int, n: int, itemsize: int, n_rhs: int) -> int:
    """The widest column tile (a multiple of the lane width that divides
    ``n``) at which ``n_rhs`` weight tiles of the whole contraction fit the
    budget; 0 if not even one lane tile of columns does."""
    return max((t for t in range(LANES, n + 1, LANES) if n % t == 0
                and k * t * itemsize * n_rhs <= WEIGHT_TILE_BYTES), default=0)


def _fits(rows, *weights) -> bool:
    """Whether one kernel call can multiply ``rows`` by these (equally
    shaped) weights: both widths of the weights whole lane tiles and the
    contraction short enough for a grid step to hold it whole."""
    k, n = weights[0].shape[1:]
    return (k % LANES == 0
            and _col_tile(k, n, rows.dtype.itemsize, len(weights)) > 0)


def kernel_can_run(rows, *weights) -> bool:
    """Whether :func:`swiglu` sends this product to a kernel: bfloat16
    operands and shapes that fit.  The kernels multiply float32 too (the
    CPU tests do), but only bfloat16 was lowered, timed and compared on the
    chip: a float32 model keeps ``ragged_dot`` and XLA's default precision
    until that is read."""
    return (all(a.dtype == jnp.bfloat16 for a in (rows, *weights))
            and _fits(rows, *weights))


def _visits(sizes, row_tiles: int, tm: int):
    """The grid's table: ``starts``/``ends`` (count,) of every group's rows,
    ``group_of``/``tile_of`` (visits,) of every (group, row tile) pair in
    which a group has rows, groups in order, and how many pairs there are.
    A group starts in the tile its predecessor ends in unless the boundary
    falls between them, so there are fewer than ``row_tiles + count``."""
    count = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visits = row_tiles + count - 1
    group_of = jnp.repeat(jnp.arange(count, dtype=jnp.int32), tiles,
                          total_repeat_length=visits)
    nth = jnp.arange(visits, dtype=jnp.int32) - (jnp.cumsum(tiles) - tiles)[group_of]
    tile_of = jnp.clip(first[group_of] + nth, 0, row_tiles - 1)
    return (starts, ends, group_of, tile_of), tiles.sum()


def visited_tiles(sizes, m: int):
    """Row tiles the kernels visit for these sizes over ``m`` sorted rows:
    one for every (group, row tile) pair in which the group has rows."""
    tm = row_tile(m)
    return _visits(sizes, _round_up(m, tm) // tm, tm)[1]


def _kernel(n_rhs, tm, epilogue, starts, ends, group_of, tile_of, lhs, *refs):
    """One visit: the group's weight tiles against one row tile, and of the
    result the rows that are the group's own."""
    from jax.experimental import pallas as pl
    rhs, out = refs[:n_rhs], refs[n_rhs]
    v = pl.program_id(1)
    a = lhs[...]
    got = epilogue(*[jnp.dot(a, w[...], preferred_element_type=jnp.float32)
                     for w in rhs])
    group = group_of[v]
    row = tile_of[v] * tm + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    mine = (row >= starts[group]) & (row < ends[group])
    # the tile's other rows are another visit's, or nobody's
    out[...] = jnp.where(mine, got.astype(out.dtype), out[...])


def _grouped(rows, weights, sizes, epilogue, out_dtype, interpret):
    """``epilogue(rows @ w_g for w in weights)`` on the rows of every held
    group g, (m, n) in ``out_dtype``; the rows are padded to whole row tiles
    inside."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    held, k = rows.shape
    n = weights[0].shape[2]
    tm = row_tile(held)
    # fedlint: disable-next-line=recompile-hazard -- shapes and dtypes only
    if any(w.dtype != rows.dtype for w in weights) or not _fits(rows, *weights):
        raise ValueError(
            f"no grouped-matmul kernel for {rows.dtype}{list(rows.shape)} rows "
            f"against {[f'{w.dtype}{list(w.shape)}' for w in weights]}: "
            "one dtype, whole lane tiles, a contraction that fits a grid step")
    m = _round_up(held, tm)
    rows = jnp.pad(rows, ((0, m - held), (0, 0)))
    tn = _col_tile(k, n, rows.dtype.itemsize, len(weights))
    table, visits = _visits(sizes, m // tm, tm)

    # the columns outermost: consecutive visits of one group ask for the
    # same weight tile, which the pipeline then does not fetch again
    rhs_spec = pl.BlockSpec(
        (None, k, tn), lambda j, v, s, e, group_of, t: (group_of[v], 0, j))
    group_bytes = sum(w.size // w.shape[0] * w.dtype.itemsize for w in weights)
    return pl.pallas_call(
        functools.partial(_kernel, len(weights), tm, epilogue),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec(
                (tm, k), lambda j, v, s, e, g, tile_of: (tile_of[v], 0))]
            + [rhs_spec] * len(weights),
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, s, e, g, tile_of: (tile_of[v], j)),
            grid=(n // tn, visits)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n * len(weights), transcendentals=0,
            bytes_accessed=weights[0].shape[0] * group_bytes
            + rows.size * rows.dtype.itemsize
            + m * n * jnp.dtype(out_dtype).itemsize),
        interpret=interpret,
        name="gated_matmul" if len(weights) > 1 else "grouped_matmul",
    )(*table, rows, *weights)[:held]


def grouped_matmul(rows, w, sizes, *, interpret: bool = False):
    """``rows`` (m, k) sorted by group, ``w`` (count, k, n), ``sizes``
    (count,) int32 → float32 (m, n): row r of held group g holds
    ``rows[r] @ w[g]``; rows past the held groups are unspecified."""
    return _grouped(rows, (w,), sizes, lambda y: y, jnp.float32, interpret)


def gated_matmul(rows, w_gate, w_up, sizes, *, limit: float = 0.0,
                 interpret: bool = False):
    """``silu(rows @ w_gate[g]) * (rows @ w_up[g])`` (the two products
    clamped where ``limit`` > 0, a static option of the kernel), computed in
    float32 and written in the rows' dtype, on the rows of every held group
    g."""
    return _grouped(rows, (w_gate, w_up), sizes,
                    functools.partial(swiglu_act, limit=limit), rows.dtype,
                    interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def swiglu_pallas(rows, w_gate, w_up, w_down, sizes, interpret=False,
                  limit=0.0):
    """:func:`swiglu_ragged` on the held rows by the two kernels; the
    backward is :func:`swiglu_ragged`'s."""
    return _swiglu_fwd(rows, w_gate, w_up, w_down, sizes, interpret, limit)[0]


def _swiglu_fwd(rows, w_gate, w_up, w_down, sizes, interpret, limit):
    act = gated_matmul(rows, w_gate, w_up, sizes, limit=limit,
                       interpret=interpret)
    return (grouped_matmul(act, w_down, sizes, interpret=interpret),
            (rows, w_gate, w_up, w_down, sizes))


def _swiglu_bwd(interpret, limit, saved, g):
    *operands, sizes = saved
    _, vjp = jax.vjp(lambda *o: swiglu_ragged(*o, sizes, limit), *operands)
    return (*vjp(g), None)


swiglu_pallas.defvjp(_swiglu_fwd, _swiglu_bwd)


# a model's layers of one shape share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("limit",))
def swiglu(rows, w_gate, w_up, w_down, sizes, limit: float = 0.0):
    """The held experts' SwiGLU over the sorted rows and the row tiles the
    kernels visited: :func:`swiglu_pallas` where the program is lowered for
    a TPU and :func:`kernel_can_run`, else :func:`swiglu_ragged` and 0.
    ``limit`` > 0 clamps the operands of ``silu(gate) * up``
    (:func:`swiglu_act`), in the kernel as in ``ragged_dot``'s form."""
    def ragged(*operands):
        return swiglu_ragged(*operands, limit), jnp.zeros((), jnp.int32)

    def kernels(*operands):
        return (swiglu_pallas(*operands, False, limit),
                visited_tiles(operands[-1], operands[0].shape[0]))

    operands = (rows, w_gate, w_up, w_down, sizes)
    # fedlint: disable-next-line=recompile-hazard -- shapes and dtypes only
    if not (kernel_can_run(rows, w_gate, w_up)
            and kernel_can_run(rows, w_down)):
        return ragged(*operands)
    return jax.lax.platform_dependent(*operands, tpu=kernels, default=ragged)


__all__ = ["swiglu", "swiglu_ragged", "swiglu_pallas", "grouped_matmul",
           "gated_matmul", "swiglu_act", "visited_tiles", "kernel_can_run",
           "row_tile"]
