"""The index-score kernel (ISSUE 36, ``ops/index_scores.py``) in interpret
mode against the XLA form it replaces in the decode step —
``models/layered.py::_index_scores_view``, the gathered key view and its two
contractions — on the same pool: ragged lengths from an empty cache to the
table's width, a retired slot, a walk cut at a sentinel, ids out of range
clamped, consecutive against shuffled pages (one copy a group against one a
page), bfloat16 and float32 pools; what ``top_mask`` makes of the columns past
a slot's length; the pool structures it takes; its counters against a hand
count.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.models import layered
from mxnet_tpu.ops import index_scores as ix
from mxnet_tpu.ops import page_walk

B, PAGE, MAXP, NL, J, DI = 4, 16, 20, 2, 4, 128
ROWS = 128                      # a group of 8 pages: the table holds 2.5
BLOCK = 32                      # fetched in blocks of 2 pages
PER, SUB = ROWS // PAGE, BLOCK // PAGE
T = PAGE * MAXP                 # 320
NPAGES = 96
LAYER = 1
RETIRED = 3


assert ROWS < T < 3 * ROWS and MAXP % PER


def _copies(row, held):
    """The copies a walk over ``held`` pages of table row ``row`` takes, by
    the rule in plain Python: a group of ``PER`` entries whose held pages
    are consecutive (and ``PER`` pages from the first lie in the pool) is
    one copy; else each block of ``SUB`` entries likewise, or a copy a held
    page."""
    def run(ids, n):
        return all(b == ids[0] + j for j, b in enumerate(ids)) \
            and 0 <= ids[0] and ids[0] + n <= NPAGES

    total = 0
    for g in range(0, held, PER):
        if run(list(row[g:min(g + PER, held)]), PER):
            total += 1
            continue
        for k in range(g, min(g + PER, held), SUB):
            ids = list(row[k:min(k + SUB, held)])
            total += 1 if run(ids, SUB) else len(ids)
    return total


def _tables(shuffled):
    """Slot 0 and slot 2 own their pages — ascending ids, or the same ids
    handed out in shuffled order —, slot 1 shares slot 2's first two pages
    (a cached prefix) and owns three more; slot 3 is retired: a sentinel
    row."""
    rng = onp.random.RandomState(5)
    pt = onp.full((B, MAXP), NPAGES, onp.int32)
    pt[0] = onp.arange(10, 10 + MAXP)
    pt[2] = onp.arange(40, 40 + MAXP)
    if shuffled:
        pt[0], pt[2] = rng.permutation(pt[0]), rng.permutation(pt[2])
    pt[1, :2] = pt[2, :2]
    pt[1, 2:5] = [70, 71, 72]
    return pt


def _pool_for(pt, rows_of, dtype):
    """A pool in which slot ``b``'s logical page ``j`` (page ``pt[b, j]``)
    holds ``rows_of[b, j]`` — the same keys at the same positions whatever
    ids the table hands out — and every other page noise."""
    rng = onp.random.RandomState(11)
    pool = rng.randn(NL, NPAGES, PAGE, 128).astype("float32")
    for b in (2, 0):            # slot 1 reads slot 2's first two pages
        for j in range(MAXP):
            pool[LAYER, pt[b, j]] = rows_of[b, j]
    for j in range(2, 5):
        pool[LAYER, pt[1, j]] = rows_of[1, j]
    return jnp.asarray(pool, dtype)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def dtype(request):
    return jnp.dtype(request.param)


@pytest.fixture(scope="module")
def case(dtype):
    """Queries, weights and the logical keys, with the kernel (interpreted)
    and the XLA form jitted once over ``(pool, table, positions)``."""
    rng = onp.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, J, DI), dtype)
    w = jnp.asarray(rng.rand(B, J) + 0.1, jnp.float32)
    rows_of = rng.randn(B, MAXP, PAGE, 128).astype("float32")

    def kernel(pool, pt, pos):
        ends = page_walk.walk_lengths(pt, pos + 1, PAGE, NPAGES)
        return ix._kernel_call(q, w, pool, jnp.int32(LAYER), pt, ends,
                               True, rows=ROWS, sub=BLOCK)

    def view(pool, pt, pos):
        s = layered._index_scores_view(q[:, None], w[:, None], pool, LAYER,
                                       pt, PAGE)[:, 0]
        seen = jnp.arange(T)[None] <= pos[:, None]
        return jnp.where(seen, s, 0.0)

    return jax.jit(kernel), jax.jit(view), rows_of


def _run(case, dtype, pos, shuffled=False):
    kernel, view, rows_of = case
    pt = _tables(shuffled)
    pool = _pool_for(pt, rows_of, dtype)
    pos = jnp.asarray(pos, jnp.int32)
    got, counts = kernel(pool, jnp.asarray(pt), pos)
    want = view(pool, jnp.asarray(pt), pos)
    return onp.asarray(got), onp.asarray(counts), onp.asarray(want)


# slot 0's position: its first token, a page's last row, a page's first,
# mid-page, either side of a group, the table's last column
@pytest.mark.parametrize("pos0", [0, 1, PAGE - 1, PAGE, 100, ROWS - 2,
                                  ROWS - 1, ROWS, T - 1])
def test_kernel_matches_the_xla_scores(case, dtype, pos0):
    """The same products (operands in the pool's dtype, float32 sums) in
    another order: equal to float32 rounding, bfloat16 pool or float32; a
    column past a slot's own position reads exactly 0."""
    pos = [pos0, 37, 300, 77]           # slot 3's is stale
    got, _, want = _run(case, dtype, pos)
    live = onp.arange(B) != RETIRED
    assert got.shape == (B, T) and onp.isfinite(got).all()
    scale = onp.abs(want).max()
    onp.testing.assert_allclose(got[live], want[live], rtol=0,
                                atol=2e-6 * scale)
    for b in onp.nonzero(live)[0]:
        assert (got[b, pos[b] + 1:] == 0).all()


def test_retired_slot_walks_nothing(case, dtype):
    """A sentinel row walks no page and starts no copy whatever its stale
    ``pos``: its scores are zeros, its counts 0 pages and 0 copies."""
    got, counts, _ = _run(case, dtype, [5, 37, 300, 77])
    assert (got[RETIRED] == 0).all()
    assert counts[RETIRED].tolist() == [0, 0, MAXP]


def test_walk_stops_at_the_first_sentinel(case, dtype):
    """A row with a hole walks as far as the hole: the columns behind it
    read 0 though ``pos`` lies past them, and no copy is started for
    them."""
    kernel, _, rows_of = case
    pt = _tables(False)
    pool = _pool_for(pt, rows_of, dtype)
    whole, _ = kernel(pool, jnp.asarray(pt), jnp.asarray([250, 37, 300, 77]))
    pt[0, 9] = NPAGES
    got, counts = kernel(pool, jnp.asarray(pt),
                         jnp.asarray([250, 37, 300, 77]))
    got, whole = onp.asarray(got), onp.asarray(whole)
    assert (got[0, :9 * PAGE] == whole[0, :9 * PAGE]).all()
    assert (got[0, 9 * PAGE:] == 0).all()
    # 9 pages: one group of 8 consecutive ids, one page more
    assert onp.asarray(counts)[0].tolist() == [9, 2, MAXP]


@pytest.mark.parametrize("bad", [NPAGES + 7, 10 ** 6, -3])
def test_every_table_read_is_clamped(case, dtype, bad):
    """An id out of range never reaches a copy as it stands.  Given a walk
    that (wrongly) runs over such entries, the kernel reads the page the id
    is CLAMPED to, page by page — a group that starts or passes outside the
    pool is no run."""
    _, _, rows_of = case
    pt = _tables(False)
    pool = _pool_for(pt, rows_of, dtype)
    pt[0, 3] = pt[0, 8] = bad
    q = jnp.asarray(onp.random.RandomState(3).randn(B, J, DI), dtype)
    w = jnp.ones((B, J), jnp.float32)
    ends = jnp.asarray([200, 0, 0, 0], jnp.int32)       # past both
    got, counts = ix._kernel_call(q, w, pool, jnp.int32(LAYER),
                                  jnp.asarray(pt), ends, True, rows=ROWS,
                                  sub=BLOCK)
    clamped = jnp.asarray(onp.clip(pt, 0, NPAGES - 1))
    want, _ = ix._kernel_call(q, w, pool, jnp.int32(LAYER), clamped, ends,
                              True, rows=ROWS, sub=BLOCK, runs=False)
    onp.testing.assert_array_equal(onp.asarray(got)[0], onp.asarray(want)[0])
    assert onp.isfinite(onp.asarray(got)).all()
    # 13 pages held, neither group consecutive any more: the blocks of two
    # with a bad id go page by page, the five others as one copy each
    assert onp.asarray(counts)[0].tolist() == [13, 9, MAXP]
    assert _copies(pt[0], 13) == 9
    flags = onp.asarray(page_walk.group_runs(jnp.asarray(pt), ends, PAGE,
                                             PER, SUB, NPAGES))
    # bit k: block k of the group is a run; 1 << 4: the whole group
    assert flags[0, :2].tolist() == [0b1101, 0b1110]
    # a run that would END outside the pool is none either: of the held
    # pages 93, 94, 95 the block 93, 94 is one, the block from 95 not
    last = jnp.asarray([[NPAGES - 3 + j for j in range(PER)]], jnp.int32)
    assert onp.asarray(page_walk.group_runs(
        last, jnp.asarray([3 * PAGE]), PAGE, PER, SUB,
        NPAGES)).tolist() == [[0b0001]]


@pytest.mark.parametrize("pos0", [PAGE + 3, ROWS + 5, T - 1])
def test_consecutive_and_shuffled_pages_score_the_same(case, dtype, pos0):
    """The same keys at the same positions under ascending ids (a group is
    ONE copy) and under shuffled ids (a copy a page, but for a block whose
    two ids happen to follow each other): the same scores, bit for bit, and
    the copies tell the two apart."""
    pos = [pos0, 37, 300, 77]
    runs, c_runs, _ = _run(case, dtype, pos, shuffled=False)
    pages, c_pages, _ = _run(case, dtype, pos, shuffled=True)
    live = onp.arange(B) != RETIRED
    onp.testing.assert_array_equal(runs[live], pages[live])
    assert (c_runs[:, 0] == c_pages[:, 0]).all()
    held = -(-(pos0 + 1) // PAGE)
    assert c_runs[0].tolist() == [held, -(-held // PER), MAXP]
    shuffled = _tables(True)
    assert c_pages[0].tolist() == [held, _copies(shuffled[0], held), MAXP]
    assert c_pages[2].tolist() == [19, _copies(shuffled[2], 19), MAXP]
    assert c_pages[2, 1] > 12 and c_runs[2, 1] == 3


@pytest.mark.parametrize("k", [8, 64, 200])
def test_nan_past_the_length_changes_no_selection(case, dtype, k):
    """``top_mask`` keys every column that is not ``seen`` to 0: with NaN
    (or anything) in the columns the kernel leaves 0, the selected set is
    the same, and it is the XLA scores' set."""
    pos = onp.array([150, 37, 300, 77])
    got, _, want = _run(case, dtype, pos)
    seen = onp.arange(T)[None] <= pos[:, None]
    poisoned = onp.where(seen, got, onp.nan).astype("float32")
    pick = jax.jit(layered.top_mask, static_argnums=2)
    a = onp.asarray(pick(jnp.asarray(got), jnp.asarray(seen), k))
    b = onp.asarray(pick(jnp.asarray(poisoned), jnp.asarray(seen), k))
    c = onp.asarray(pick(jnp.asarray(want), jnp.asarray(seen), k))
    live = onp.arange(B) != RETIRED
    onp.testing.assert_array_equal(a, b)
    assert (a.sum(axis=1) == k).all()
    # the XLA scores differ in their last bits: the same set wherever the
    # k-th and the next score lie further apart than that
    for r in onp.nonzero(live)[0]:
        s = onp.sort(want[r][seen[r]])[::-1]
        if len(s) > k and s[k - 1] - s[k] < 1e-4 * abs(s[0]):
            continue
        onp.testing.assert_array_equal(a[r] & seen[r], c[r] & seen[r])


@pytest.mark.parametrize("lanes, dtype, page, num_pages, ok", [
    (128, "bfloat16", 16, 65536, True),     # the dots3 index-key pool
    (128, "float32", 8, 256, True),
    (256, "bfloat16", 32, 1024, True),
    (128, "bfloat16", 8, 1024, False),      # half a bfloat16 sublane tile
    (64, "float32", 8, 1024, False),        # half a lane tile
    (128, "int8", 32, 1024, False),
    (128, "float32", 24, 1024, False),      # pages do not fill a group
    (128, "float32", 8, 128, False),        # a pool smaller than a group
], ids=["dots3", "f32_page8", "wide_rows", "short_page", "narrow_rows",
        "int8", "odd_page", "tiny_pool"])
def test_supported_pool_structures(lanes, dtype, page, num_pages, ok):
    assert ix.supports(lanes, dtype, page, num_pages) is ok


@pytest.mark.parametrize("shuffled", [False, True], ids=["runs", "shuffled"])
def test_counters_against_a_hand_count(case, dtype, shuffled):
    """Per slot: the pages that hold positions ``0 .. pos``, the copies the
    walk started (one a group of consecutive ids, one a page otherwise) and
    the table's width."""
    pos = [ROWS + 40, 37, 300, 77]
    _, counts, _ = _run(case, dtype, pos, shuffled)
    pages = [-(-(p + 1) // PAGE) for p in pos[:3]]
    assert counts[:, 0].tolist() == pages + [0]
    assert (counts[:, 2] == MAXP).all()
    table = _tables(shuffled)
    assert counts[:, 1].tolist() == [_copies(table[b], pages[b])
                                     for b in range(3)] + [0]
    if not shuffled:
        # slot 0: a whole group and a part; slot 1: ids 40, 41, 70: the
        # block 40, 41 and the page 70; slot 2: two whole groups and a part
        # of three pages
        assert counts[:, 1].tolist() == [2, 2, 3, 0]
