"""The walk the paged kernels share (``ops/page_walk.py``), driven on its own
in interpret mode: a toy body that copies each group's rows out, through
``walk`` with each copy routine, over shuffled pages.  Slots of 0, 1 and 3
groups come in every order, so a slot that walks nothing lies between two
that do and the one fetch in flight across slots is handed on past it.
The rows that come out are ``np.take`` of the pool over the table.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mxnet_tpu.ops import page_walk

PAGE, PER, SUB, LANES = 8, 4, 2, 128
ROWS = PAGE * PER               # a group: 4 pages of 8 rows
MAXP = 3 * PER                  # a table row holds 3 groups
NPAGES, NL, LAYER = 40, 2, 1
# a slot's walk end for its number of groups: one group's last page partly
# held; three groups of which the last holds three pages, the last partly
ENDS = {0: 0, 1: ROWS - 3, 3: 2 * ROWS + 2 * PAGE + 5}


def _table(groups, rng):
    """Each slot's held pages in shuffled order, the rest sentinel; the
    slot of three groups holds a whole group in a run and a block in a run
    beside its shuffled pages, so ``run_copy`` takes every kind of copy."""
    pt = onp.full((len(groups), MAXP), NPAGES, onp.int32)
    for b, n in enumerate(groups):
        held = -(-ENDS[n] // PAGE)
        pt[b, :held] = rng.choice(NPAGES, held, replace=False)
        if n == 3:
            pt[b, :PER] = onp.arange(20, 20 + PER)
            pt[b, PER:PER + SUB] = [5, 6]
    return pt


def _walk(kind, pool, pt, ends):
    B = pt.shape[0]
    groups = MAXP // PER
    run = kind == "run"

    def kernel(layer_ref, pt_ref, end_ref, run_ref, pool_ref, out_ref,
               cnt_ref, buf, state, sems):
        b = pl.program_id(0)
        nslots = pl.num_programs(0)
        end = end_ref[b]
        ngroups = pl.cdiv(end, ROWS)
        if run:
            copy = page_walk.run_copy(
                pool_ref, buf, sems, pt_ref, end_ref, run_ref, layer_ref[0],
                page=PAGE, maxp=MAXP, num_pages=NPAGES, per=PER, sub=SUB,
                groups=groups)
        else:
            copy = page_walk.page_copy(
                ((pool_ref, buf),), sems, pt_ref, end_ref, layer_ref[0],
                page=PAGE, maxp=MAXP, num_pages=NPAGES, per=PER)

        def setup():
            out_ref[...] = jnp.zeros_like(out_ref)
            return page_walk.next_slot(
                b, nslots, lambda s: pl.cdiv(end_ref[s], ROWS))

        def body(g, at):
            rows = buf[at].reshape(ROWS, LANES)
            row = g * ROWS + jax.lax.broadcasted_iota(jnp.int32, rows.shape,
                                                      0)
            out_ref[0, pl.ds(g * ROWS, ROWS), :] = jnp.where(
                row < end, rows, 0.0)

        page_walk.walk(state, copy, b, ngroups, setup, body,
                       first_step=b == 0, nslots=nslots, zero=(buf,),
                       counts=cnt_ref if run else None)

    buf = (2, PER, PAGE, LANES) if run else (2, ROWS, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((1, MAXP * PAGE, LANES),
                                lambda b, *_: (b, 0, 0)),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        scratch_shapes=[pltpu.VMEM(buf, jnp.float32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,) if run else (1, 2))])
    flags = page_walk.group_runs(pt, ends, PAGE, PER, SUB, NPAGES)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, MAXP * PAGE, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((B, 2), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=True,
    )(jnp.asarray([LAYER], jnp.int32), pt.reshape(-1), ends,
      flags.reshape(-1), pool)


@pytest.mark.parametrize("groups", list(itertools.permutations((0, 1, 3))),
                         ids=lambda g: "-".join(map(str, g)))
@pytest.mark.parametrize("kind", ["page", "run"])
def test_walk_copies_each_slots_rows_in_table_order(kind, groups):
    rng = onp.random.RandomState(sum(g * 4 ** i for i, g in enumerate(groups)))
    pool = rng.randn(NL, NPAGES, PAGE, LANES).astype(onp.float32)
    pt = _table(groups, rng)
    ends = onp.asarray([ENDS[n] for n in groups], onp.int32)
    got, counts = _walk(kind, jnp.asarray(pool), jnp.asarray(pt),
                        jnp.asarray(ends))
    want = onp.take(pool[LAYER], onp.minimum(pt, NPAGES - 1), axis=0).reshape(
        len(groups), MAXP * PAGE, LANES)
    want[onp.arange(MAXP * PAGE)[None, :] >= ends[:, None]] = 0.0
    onp.testing.assert_array_equal(onp.asarray(got), want)
    if kind == "run":
        # copies are counted for the slot they fetch, whoever started them
        assert (onp.asarray(counts)[:, 1] > 0).tolist() == \
            [n > 0 for n in groups]
