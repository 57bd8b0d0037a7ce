"""Sort-based sparse group-by: high-cardinality GROUP BY on device.

SURVEY.md §8.4 hard part #1: static shapes force a choice of group-table
size. The dense path (kernels.groupby) materializes the full mixed-radix
space [K] and stops at the dense budget; beyond it the reference-shaped
answer would be a hash exchange, but sorting is the TPU-idiomatic move —
XLA's sort is fast on TPU and everything stays static-shaped:

  1. mixed-radix key in int64 (the radix product may exceed int32; where
     it does not, or a word of a several-word key holds 31 bits or fewer,
     that word is int32: `key_word_dtypes`, one u32 sort operand in place
     of two); masked rows get the +inf sentinel (the maximum of word 0's
     dtype) so they sort to the tail. A group
     space of 2^62 or more takes MORE THAN ONE such word (`pack_key_words`
     says which dimension lies in which): the sort compares the words in
     turn (`num_keys`), the SENTINEL stands in word 0 alone, a run ends
     where any word changes and `_keys` is one table a word (`key_names`).
     Everything below holds a word for a word; one word is the program
     it always was, text for text;
  2. one multi-operand `lax.sort` carries the key and every aggregate
     input along (not the mask: a sorted row is masked exactly where its
     key is the sentinel; not stable: no table depends on the order of
     rows inside a run);
  3. group boundaries (key[i] != key[i-1]) -> cumsum -> dense ids in
     [0, n_unique); ids clip to a `cap` slot table (+1 overflow slot that
     also swallows the sentinel tail). After the sort a group is a
     contiguous run: `starts[g]`, the run's first row, comes from a
     second, one-operand sort of the first rows' positions;
  4. the [cap] tables are READ AT THE RUN BOUNDARIES: a row count is
     starts[g+1] - starts[g], a key is the run's first row's, an integer
     sum or count is the difference of an inclusive prefix sum at the
     run's two ends (wrapping arithmetic: exact so long as the run's own
     sum fits the prefix's word), an integer min / max of a column stored
     in 32 bits or fewer is read at the run's last row from a running
     maximum of the one word (run id << b) | code(value): the run ids do
     not decrease, so the plain maximum is segmented by construction
     (`ext_word_dtype`). No row is scattered. What neither gives keeps a
     segment reduce over the sorted ids — a floating-point sum (the
     prefix's rounding error is not the group's), a min / max of a
     column stored in 64 bits or of a double, the sketches' [cap, m]
     state — and `sparse_reduce_form` says "scatter" of such a plan. Slot
     i holds the i-th smallest present group key, so results are already
     compact AND sorted;
  5. "_count" reports the true unique count — if it exceeds cap the
     dispatch re-runs with the next power of two (same adaptive-cap
     pattern as executor.packing).

Which program of a plan that is, is one `SparseProgram` value (its
fields say what each variant is: a TopN's or a HAVING's cut on the
device, the narrow sums, how the whole tables are read; chosen by
`executor.sparse_dispatch.choose_program`), `sparse_group_reduce` says
what each does to the tables, and `program_words` what the record says
of it.

Multi-chip merge (P2, SURVEY.md §3.5): the chips' compacted tables are
merged on the device (`merge_device`) or, with a sketch's state, by the
host broker (`merge_sparse`).
"""

from __future__ import annotations

import dataclasses
import functools
import operator

import jax
import jax.numpy as jnp
import numpy as np

from tpu_olap.kernels import hll as hll_mod
from tpu_olap.kernels import theta as theta_mod
from tpu_olap.kernels.groupby import (UnsupportedAggregation, _hash_fields,
                                      _ident, stage_scope)

SENTINEL = np.int64(np.iinfo(np.int64).max)


# bits of one key word: every word stays under 2^62, so the SENTINEL
# (2^63 - 1) is never a key and a radix step cannot overflow
KEY_WORD_BITS = 62


def dim_bits(size) -> int:
    """Bits that hold the ids 0..size-1 of one dimension."""
    return (int(size) - 1).bit_length()


def pack_key_words(sizes) -> tuple:
    """The positions of `sizes` (the id domains of a group key, in radix
    order) packed into the fewest int64 words: a tuple a word of positions,
    ascending. A space under 2^62 is ONE word of every position, the exact
    mixed radix the sparse key always was. Past it a dimension takes
    `dim_bits` of a word (a radix of the next power of two: the program
    then holds the domain's width and not its exact size, so a table whose
    minimum or maximum moves from load to load keeps its program), and the
    positions are laid first-fit in descending width: GROUP BY order would
    strand room (TPC-H Q18's five columns take three words in that order,
    two by width), and a word costs two u32 sort operands. The word that
    holds position 0 comes first."""
    total = 1
    for s in sizes:
        total *= int(s)
    if total < (1 << KEY_WORD_BITS):
        return (tuple(range(len(sizes))),)
    words = []   # [bits used, positions]
    for i in sorted(range(len(sizes)), key=lambda i: -dim_bits(sizes[i])):
        need = dim_bits(sizes[i])
        for w in words:
            if w[0] + need <= KEY_WORD_BITS:
                w[0] += need
                w[1].append(i)
                break
        else:
            words.append([need, [i]])
    return tuple(sorted(tuple(sorted(w[1])) for w in words))


def key_radix(sizes, words) -> tuple:
    """The radix each position of `sizes` has in its word: its size where
    the key is one word, the next power of two where it is several."""
    if len(words) == 1:
        return tuple(int(s) for s in sizes)
    return tuple(1 << dim_bits(s) for s in sizes)


def key_bits(sizes) -> int:
    """Bits the ids of a group key's dimensions take together."""
    return sum(dim_bits(s) for s in sizes)


def key_names(n_words: int) -> tuple:
    """The compact tables that hold a key's words, word 0 first."""
    return ("_keys",) + tuple(f"_keys{w}" for w in range(1, n_words))


def key_word_dtypes(sizes, words) -> tuple:
    """The dtype each word of a group key rides the sort in, a word: int32
    where every value the word can hold fits one, else int64. Known where
    the program is built: `sizes` are the id domains (radix order), `words`
    their `pack_key_words`. The bound is exact: the largest value of a word
    is its positions' largest ids (size - 1) combined under `key_radix`
    (for a one-word key that is the radix product less one). Word 0 also
    carries the sentinel of the masked rows, the maximum of its dtype, so
    its values must stay BELOW 2^31 - 1 (a one-word space of up to
    2^31 - 1 groups; 31 bits of a several-word key only where the exact
    sizes leave the last value free); a later word carries none and may
    reach 2^31 - 1 (31 bits). An id is below its domain's size by
    construction, which the int64 key rests on as well. Which dimension
    sits in which word is `pack_key_words`' alone: this rule never moves
    one."""
    radix = key_radix(sizes, words)
    out = []
    for w, positions in enumerate(words):
        top = 0
        for i in positions:
            top = top * radix[i] + int(sizes[i]) - 1
        limit = np.iinfo(np.int32).max - (1 if w == 0 else 0)
        out.append(np.dtype(np.int32 if top <= limit else np.int64))
    return tuple(out)


def key_sort_bits(sizes, words) -> list:
    """`key_word_dtypes` as the record says it: bits a word, `[32]`,
    `[64]`, `[64, 32]`."""
    return [8 * dt.itemsize for dt in key_word_dtypes(sizes, words)]


def build_group_key64(ids, sizes, words=None):
    """Mixed-radix combine into one key word, int64, or with `words`
    (`pack_key_words` of `sizes`) into each word's `key_word_dtypes`: a
    word whose values fit 31 bits is combined in int32 (no emulated
    64-bit multiply-add a row), any other in int64. One word: the array;
    several: a tuple, each the combine of its positions' ids under
    `key_radix`. Callers without `words` guard product < 2^62."""
    def combine(ids, radix, dtype):
        word = getattr(jnp, dtype.name)
        key = None
        for i, s in zip(ids, radix):
            i = i.astype(word)
            key = i if key is None else key * word(s) + i
        return jnp.zeros((), word) if key is None else key

    total = 1
    for s in sizes:
        total *= int(s)
    dtypes = (np.dtype(np.int64),) if words is None \
        else key_word_dtypes(sizes, words)
    if len(dtypes) > 1:
        radix = key_radix(sizes, words)
        return tuple(combine([ids[i] for i in w], [radix[i] for i in w], dt)
                     for w, dt in zip(words, dtypes)), total
    if total >= (1 << KEY_WORD_BITS):
        raise UnsupportedAggregation(
            f"group space {total} overflows the int64 key")
    return combine(ids, sizes, dtypes[0]), total


def _key_words(key) -> tuple:
    """A key as the tuple of its words (one array: one word)."""
    return tuple(key) if isinstance(key, (tuple, list)) else (key,)


def _sentinel(word):
    """What a masked row holds in word 0 of the key, and an empty slot's
    key inside the program: the maximum of the word's dtype (of an int64
    word the SENTINEL)."""
    dtype = np.dtype(word.dtype)
    return dtype.type(np.iinfo(dtype).max)


def _changes(skeys):
    """[N-1] bool: where a sorted key differs from the row before it, in
    any word (each compared in its own width)."""
    return functools.reduce(operator.or_,
                            [w[1:] != w[:-1] for w in skeys])


def _running(x, kind: str, axis=0):
    """Inclusive running sum (`kind` "add") or maximum ("max") of x along
    `axis` on the device: `lax.cumsum` / `lax.cummax` as JAX itself lowers
    them for the TPU, one reduce-window over the whole axis, bound here
    and not through them. `jnp.cumsum`, `lax.cumsum` and `lax.cummax`
    lower as out-of-line functions, and an op inside one loses the
    caller's `named_scope` (its op_name is `reduce_window_sum` and no
    more): a capture could then not tell the run ids' prefix sum from an
    aggregate's."""
    n = x.shape[axis]
    if n == 0:
        return x
    dims, pads = [1] * x.ndim, [(0, 0)] * x.ndim
    dims[axis], pads[axis] = n, (n - 1, 0)
    init = 0 if kind == "add" else np.iinfo(x.dtype).min
    return jax.lax.reduce_window(x, np.array(init, x.dtype),
                                 getattr(jax.lax, kind), dims,
                                 [1] * x.ndim, pads)


def _sorted_segments(skeys, cap):
    """The row reduction's run ids and count: gid clips into the dropped
    overflow+sentinel slot `cap`. `skeys`: the sorted key's words (a
    masked row holds the sentinel of word 0's dtype there; what its other
    words hold starts runs in the tail that no slot keeps)."""
    sentinel = _sentinel(skeys[0])
    boundary = jnp.concatenate([
        jnp.ones((1,), bool),
        _changes(skeys),
    ])
    gid = _running(boundary.astype(jnp.int32), "add") - 1
    count = (boundary & (skeys[0] != sentinel)).sum(dtype=jnp.int32)
    gid = jnp.where((gid < cap) & (skeys[0] != sentinel), gid, cap)
    return gid, count


def _run_starts(skey, cap, riders=()):
    """-> (starts, rode). starts: [cap + 1] int32, starts[g] the first row
    of the g-th run of equal
    keys (`skey`: an array, or a tuple of the key's words); for a slot
    past the last present group (and for g == cap when
    nothing overflows) the row where the SENTINEL tail begins, so an empty
    slot is an empty run. The first rows' positions, every other row
    standing in as the tail's first, sorted: a second, one-operand sort
    whose cost does not depend on cap (a binary search of the slot
    numbers in the run ids costs 21 ns a slot a round: less up to 2^16
    slots, 1.2 s at the budget's 2^21), and no row is scattered.

    rode: what `riders` hold there, [N] arrays a whole [cap] table is
    read from at the runs' boundaries (a prefix sum, a running word),
    each as its [cap + 1] values at the row before each of `starts`, 0
    before row 0. A gather at ascending indices that a sort has just
    compacted IS a compaction, so each rides that sort as one more
    operand: shifted by
    one row, and on every row that is not a run's first (and in the
    padding) its value before the tail's first row, which is what a slot
    past the present groups reads. Rows that tie on the position carry
    the same fill, so the order an unstable sort leaves them in cannot
    show. No index is paid for; an operand over the rows sorted is
    (`BOUNDARY_SORT_MAX_ROWS_PER_SLOT`)."""
    skeys = _key_words(skey)
    n = skeys[0].shape[0]
    valid = skeys[0] != _sentinel(skeys[0])
    first = valid & jnp.concatenate([jnp.ones((1,), bool), _changes(skeys)])
    tail = valid.sum(dtype=jnp.int32)
    pos = jnp.where(first, jnp.arange(n, dtype=jnp.int32), tail)
    if n < cap + 1:
        pos = jnp.concatenate([pos, jnp.broadcast_to(tail, (cap + 1 - n,))])
    if not riders:
        return jax.lax.sort(pos, is_stable=False)[:cap + 1], []

    def ride(x):
        fill = jnp.where(tail > 0, x[jnp.maximum(tail - 1, 0)], 0)
        before = jnp.concatenate([jnp.zeros((1,), x.dtype), x[:-1]])
        x = jnp.where(first, before, fill)
        if n < cap + 1:
            x = jnp.concatenate([x, jnp.broadcast_to(fill, (cap + 1 - n,))])
        return x
    with stage_scope("prefix"):
        riders = [ride(x) for x in riders]
    starts, *rode = jax.lax.sort((pos, *riders), num_keys=1,
                                 is_stable=False)
    return starts[:cap + 1], [x[:cap + 1] for x in rode]


# Rows sorted a slot of the compact table (n / (cap + 1)) up to which a
# whole [cap] table read at the runs' boundaries rides `starts`' sort
# (`_run_starts`' riders) and past which it is a gather after it. A gather
# is paid by the index, a sort operand by the row sorted. Measured on a
# v5e over 60,030,976 rows, a narrow sum ranked (`tools/
# sweep_group_reduce.py --boundary-read gather sorted`, PERF.md section 6,
# PR 43), `sorted` less `gather`: +64.9 ms at 2^18 slots (229 rows a slot),
# +39.3 at 2,000,001 (30), -0.2 at 2^22 (14.3), -238.3 at 2^24 (3.6): the
# operand costs ~68 ms whatever the cap and the two cross at 14 rows a
# slot (an int64 table is two u32 halves gathered against two operands:
# the same ratio by PR 41's prices, not swept). 8 leaves a margin of 1.8:
# TPC-H Q18's 2^24 slots over 62M padded rows (3.7) ride; the Druid TopNs'
# 2,000,001 (10.2 and 31) and q3 / q10's 2^18 (59-150) keep the gather
# and the program they had
BOUNDARY_SORT_MAX_ROWS_PER_SLOT = 8


def boundary_spelling(n: int, cap: int) -> str:
    """"sorted" | "gather": how a program over `n` sorted rows reads a
    whole [cap] table at the runs' boundaries. Two shapes, and nothing
    else."""
    return "sorted" if n <= BOUNDARY_SORT_MAX_ROWS_PER_SLOT * (cap + 1) \
        else "gather"


# rows a block of the 64-bit running maximum: XLA:TPU compiles a
# one-dimensional one of 4,096 int64 in seconds, of a million in a minute,
# and falls over at sixty million (PERF.md section 6, PR 37)
_SCAN_BLOCK = 4096


def _running_max(word):
    """Inclusive running maximum of [N] non-negative words. An int32 one is
    `lax.cummax` (`_running`), which XLA:TPU lowers as it lowers the prefix
    sums' `cumsum`; an int64 one is the same along blocks of `_SCAN_BLOCK`
    rows,
    each block raised to the running maximum of the blocks before it."""
    n = word.shape[0]
    if word.dtype.itemsize <= 4 or n <= _SCAN_BLOCK:
        return _running(word, "max")
    blocks = -(-n // _SCAN_BLOCK)
    # lax.pad, not jnp.pad: the latter is an out-of-line function too
    rows = jax.lax.pad(word, np.array(0, word.dtype),
                       [(0, blocks * _SCAN_BLOCK - n, 0)]) \
        .reshape(blocks, _SCAN_BLOCK)
    inner = _running(rows, "max", axis=1)
    before = jnp.concatenate([jnp.zeros((1,), word.dtype),
                              _running_max(inner[:-1, -1])])
    return jnp.maximum(inner, before[:, None]).reshape(-1)[:n]


def _ext_running(v, counted, gid, kind, col_dtype, word):
    """[N] `word`s an exact min / max of v over the sorted runs is read
    from at a run's last row: the running maximum of
    (run id << b) | code(v). gid does not decrease, so at that row the
    maximum holds the run's own id above the largest code seen in the
    run; `counted` (None: every row) is False on the rows the aggregator
    leaves out, which code as 0."""
    lim, b = np.iinfo(col_dtype), np.iinfo(col_dtype).bits + 1
    v = v.astype(word)
    code = v - lim.min + 1 if kind == "max" else lim.max - v + 1
    if counted is not None:
        code = jnp.where(counted, code, 0)
    return _running_max((gid.astype(word) << b) | code)


def _ext_value(running, kind, col_dtype):
    """The min / max under `_ext_running`'s words as they stand at runs'
    last rows (garbage of an empty run's)."""
    lim, b = np.iinfo(col_dtype), np.iinfo(col_dtype).bits + 1
    code = running & ((1 << b) - 1)
    return code - 1 + lim.min if kind == "max" else lim.max + 1 - code


def sparse_reduce_form(plans, col_dtypes, cap) -> str:
    """Which program the sparse reduce is, from static facts alone — the
    plan's aggregate kinds and accumulator dtypes, the dtype each
    aggregated column is stored at (`col_dtypes`: field -> dtype; a name
    the dataset does not store, a virtual column, is materialised in 64
    bits and may be left out) and the compact table's cap: "boundary"
    where every [cap] table is read at the boundaries of the sorted runs,
    "scatter" where an aggregate still scatters rows into its table — a
    sketch's [cap, m] state, a floating-point sum (a difference of prefix
    sums would carry the prefix's rounding error, not the group's), a min
    / max of a double or of a column stored in 64 bits."""
    return "boundary" if all(
        prefix_summed(p) or _ext_word(p, col_dtypes, cap) is not None
        for p in plans) else "scatter"


def ext_word_bits(plans, col_dtypes, cap):
    """Bits of the widest word a min / max of `plans` is read from at the
    runs' last rows (32 | 64); None where none is."""
    words = [w for p in plans
             if (w := _ext_word(p, col_dtypes, cap)) is not None]
    return max(8 * w.itemsize for w in words) if words else None


def prefix_summed(p) -> bool:
    """A count or an integer sum: a difference of prefix sums at the
    run's two ends."""
    return p.kind == "count" or (
        p.kind == "sum" and np.issubdtype(np.dtype(p.acc_dtype), np.integer))


def _ext_word(p, col_dtypes, cap):
    if p.kind not in ("min", "max"):
        return None
    return ext_word_dtype(col_dtypes.get(p.fields[0]), p.acc_dtype, cap)


def ext_word_dtype(col_dtype, acc_dtype, cap):
    """The dtype of the word an integer min / max is read from at the
    runs' last rows, or None where it keeps the segment reduce. A column
    stored in w <= 32 bits has 2^w values: code(v) = v - lowest + 1 for a
    max and highest - v + 1 for a min, 0 on a row the aggregator leaves
    out, takes w + 1 bits under the run id's cap.bit_length(). The width
    follows from those two facts: an int32 word where they fit 31 bits,
    an int64 word where they fit 63, else (a column stored in 64 bits, a
    double accumulator) no word."""
    if col_dtype is None or not _narrow_int(col_dtype, acc_dtype):
        return None
    bits = int(cap).bit_length() + np.iinfo(col_dtype).bits + 1
    if bits <= 31:
        return np.dtype(np.int32)
    return np.dtype(np.int64) if bits <= 63 else None


def _ext_dtype(col_dtype, acc_dtype):
    """The width a min / max rides the sort at: an extreme never leaves
    its input's range, so an integer column stored in 32 bits or fewer
    rides as int32 and is widened to the accumulator once a slot. Such a
    column is read at the runs' last rows (`ext_word_dtype`); what keeps
    the segment reduce rides at the accumulator's width: XLA:TPU's scatter
    takes 8-9 ns a row a 32-bit element and 81-91 an int64, which it
    emulates (PERF.md section 6, PRs 28 and 30)."""
    return np.dtype(np.int32 if _narrow_int(col_dtype, acc_dtype)
                    else acc_dtype)


def _narrow_int(col_dtype, acc_dtype) -> bool:
    col_dtype = np.dtype(col_dtype)
    return np.dtype(acc_dtype).kind == "i" and col_dtype.kind in "iu" \
        and np.can_cast(col_dtype, np.int32)


def _narrow_sum(p, col_dtype) -> bool:
    """A sum that may ride as ONE int32 word where its program is asked to
    (`sparse_group_reduce`'s `narrow`): a 64-bit integer accumulator over
    a column stored in 32 bits or fewer (`col_dtype` None: a virtual
    column, materialised in 64 bits)."""
    return p.kind == "sum" and col_dtype is not None \
        and np.dtype(p.acc_dtype).itemsize > 4 \
        and _narrow_int(col_dtype, p.acc_dtype)


def narrow_sums(plans, col_dtypes) -> bool:
    """Whether the narrow program of `plans` differs from the wide one:
    some sum is one `_narrow_sum` says may ride as int32."""
    return any(_narrow_sum(p, col_dtypes.get(p.fields[0]))
               for p in plans if p.kind == "sum")


def sum_word_bits(plans, col_dtypes, narrow: bool):
    """Bits of the widest word an integer sum of `plans` rides the sort,
    its prefix sum and its boundary gather at, in the program `narrow`
    asks for: 32 where every one is a column stored in 32 bits or fewer
    and the program is the narrow one, else the accumulator's 64; None
    where the plan has no integer sum (a count's prefix is int32
    whatever it is asked)."""
    bits = [32 if narrow and _narrow_sum(p, col_dtypes.get(p.fields[0]))
            else 8 * np.dtype(p.acc_dtype).itemsize
            for p in plans if p.kind == "sum" and prefix_summed(p)]
    return max(bits) if bits else None


@dataclasses.dataclass(frozen=True)
class SparseProgram:
    """What fixes one sparse program of a plan, beside the plan: the one
    static argument of `sparse_group_reduce` and of lowering's
    `make_sparse_kernel`, the suffix of the program's jit key (as itself:
    frozen and hashable), and what `program_words` reads the record's
    words from. `executor.sparse_dispatch.choose_program` makes it.
    `cap`: slots of the compact table (None: the program that counts the
    groups present and builds no table); `top`: (metric, threshold,
    inverted) of a TopN whose threshold the program applies; `kept`: rows
    of the bucket the groups a device HAVING lets through are compacted
    into; `narrow`: an integer sum of a column stored in 32 bits or fewer
    rides as ONE int32 word; `boundary`: `boundary_read` of the cap and
    the rows sorted ("sorted" | "gather" | None); `window`: segments of
    the window the program slices its inputs to, where there is one."""
    cap: int | None
    top: tuple | None = None
    kept: int | None = None
    narrow: bool = False
    boundary: str | None = None
    window: int | None = None


def sparse_group_reduce(key, mask, env, plans, consts, program,
                        having=None):
    """[N] keys + mask -> compacted per-group partials: the table program
    of `program` (a `SparseProgram`; its `cap` is the table's slots).

    Returns {"_keys": [cap] int64 (SENTINEL marks empty slots),
             "_count": [] int32 true unique count,
             "_rows": [cap], <agg name>: [cap] or [cap, m], ...}.

    `key` is one [N] array, or the tuple of a wide key's words
    (`build_group_key64` with `words`; the module's step 1): the tables
    gain one a further word (`key_names`: `_keys1`, ...; what they hold
    in an empty slot is not defined: `_keys` says which slots are
    present). A word rides the sort, the boundary test and the gather of
    its table in its own width (`key_word_dtypes`); the tables leave as
    int64 whatever the words rode as, an empty slot's `_keys` the int64
    SENTINEL: they are the int64 program's to the bit.

    With `program.top` = (metric, threshold, inverted), the rows of that
    table a TopN by `metric` (a count or a sum of `plans`) keeps, as
    [min(threshold, cap)] tables in rank order: the program ranks first.
    Of the [cap] tables it builds the metric's alone (and `_rows`, which
    says which slots are present), `top_k` picks the kept slots, and every
    other table is read at those: the boundary readers below take the
    slots they read at, every slot where there is no `top`. `_count` stays
    the table's own (the cap-overflow probe reads it); a rank past the
    present groups holds the SENTINEL key and the identities of the empty
    slot it points at.

    With `program.kept` and `having` = (test, names), the plan's
    `compile_having`, the groups a HAVING lets through, as [kept] tables
    in ascending slot (= key) order: the predicate in the rank's place.
    Of the [cap] tables only those of the aggregates `names` that `test`
    reads are built (and `_rows`); `_kept` counts the present slots that
    pass, which are compacted into the first `_kept` of `kept` rows (the
    dispatch re-runs with a larger bucket where they do not fit, as it
    does for `cap`),
    and every other table is read at those slots. A row past `_kept`
    holds the SENTINEL key and no rows.

    With `program.narrow`, an integer sum of a column stored in 32 bits
    or fewer (`_narrow_sum`) rides the sort as ONE int32 operand, takes
    its prefix sum in int32 and is read with one gather a boundary, and
    the tables hold `_narrow_ok`, a scalar: whether the longest run times
    the largest |value| of each such column is at most 2^31 - 1. Where it
    is, no group's sum leaves int32, the wrapped int32 difference IS the
    sum, and
    the tables (widened to the accumulator a slot) are the wide program's
    to the bit; where it is not (or `_count` passes `cap`: a run past the
    cap is not among `_rows`) the caller runs the wide program. A plan
    with no such sum gives the program it gives without `narrow`, text
    for text.

    `program.boundary` is `boundary_read` of this program's plans, cap
    and rows: with "sorted" the prefix sums and running words a whole
    [cap] table is read from ride `starts`' sort (`_run_starts`' riders);
    else each is a gather after it. The tables are the same to the bit.
    """
    cap, top, narrow = program.cap, program.top, program.narrow
    # the plan's predicate cuts only where the program has a bucket for it
    having = None if program.kept is None else (*having, program.kept)
    # the mask does not ride the sort: a sorted row is masked exactly
    # where its key is the SENTINEL
    slots = {}
    words = {}   # "w:" + a min / max's name -> (operand, word, stored, p)
    narrowed = []   # the sums that ride as one int32 word
    prefixed = {}   # operand read as a prefix sum -> the prefix's word

    def carry(name, arr):
        if name not in slots:
            slots[name] = len(operands)
            operands.append(arr)

    kwords = _key_words(key)
    with stage_scope("sort"):
        operands = [jnp.where(mask, kwords[0], _sentinel(kwords[0])),
                    *kwords[1:]]
        for p in plans:
            m = mask
            if p.filter_fn is not None:
                with stage_scope("filter"):
                    m = mask & p.filter_fn(env, consts)
            if p.kind == "count":
                if p.filter_fn is not None:
                    carry(f"m:{p.name}", m)
                    # a count is at most N: an int32 prefix holds it
                    prefixed[f"m:{p.name}"] = np.int32
                continue
            if p.kind in ("sum", "min", "max"):
                x = env["cols"][p.fields[0]]
                nulls = env["nulls"].get(p.fields[0])
                mm = m & ~nulls if nulls is not None else m
                if p.kind == "sum":
                    dt = p.acc_dtype
                    if narrow and _narrow_sum(p, x.dtype):
                        dt = np.dtype(np.int32)
                        narrowed.append(p.name)
                    carry(f"v:{p.name}", jnp.where(mm, x, 0).astype(dt))
                    if prefix_summed(p):
                        prefixed[f"v:{p.name}"] = dt
                else:
                    dt = _ext_dtype(x.dtype, p.acc_dtype)
                    word = ext_word_dtype(x.dtype, p.acc_dtype, cap)
                    if word is not None:
                        # read at the runs' last rows, where a row left
                        # out codes as 0: the column rides unfilled, once
                        # for every min and max of it
                        operand = f"x:{p.fields[0]}:{dt}"
                        words[f"w:{p.name}"] = (operand, word,
                                                np.dtype(x.dtype), p)
                        carry(operand, x.astype(dt))
                    else:
                        carry(f"v:{p.name}",
                              jnp.where(mm, x.astype(dt),
                                       _ident(dt, p.kind)))
                    if p.filter_fn is not None or nulls is not None:
                        # mm == mask otherwise: the non-null count IS
                        # _rows, so skip both the sort operand and the
                        # reduction
                        carry(f"nn:{p.name}", mm)
                        prefixed[f"nn:{p.name}"] = np.int32
            elif p.kind in ("hll", "theta"):
                h, valid = _hash_fields(env, p, m, jnp, consts)
                carry(f"h:{p.name}", h)
                carry(f"hv:{p.name}", valid)
            else:
                raise UnsupportedAggregation(
                    f"sparse group-by does not support {p.kind!r}")

        # no table depends on the order of the rows inside a run, so the
        # sort need not be stable: XLA spells stability as one more
        # operand, an iota that breaks ties
        sorted_ops = list(jax.lax.sort(tuple(operands),
                                       num_keys=len(kwords),
                                       is_stable=False))

    skeys = tuple(sorted_ops[:len(kwords)])

    with stage_scope("runs"):
        gid, count = _sorted_segments(skeys, cap)

    scans = {}   # name -> the [N] prefix sum or running word, built once

    def scan(name):
        """What a table is read from at the runs' boundaries, built once:
        the inclusive prefix sum of the sorted operand `name` in its word
        (`prefixed`: the accumulator's, or a narrowed sum's and a count's
        int32), or the running word of a min / max (`words`)."""
        if name not in scans:
            with stage_scope("prefix"):
                if name in prefixed:
                    scans[name] = _running(sorted_ops[slots[name]]
                                           .astype(prefixed[name]), "add")
                else:
                    operand, word, col_dtype, p = words[name]
                    scans[name] = _ext_running(
                        sorted_ops[slots[operand]], counted(p), gid,
                        p.kind, col_dtype, word)
        return scans[name]

    def counted(p):
        # the rows a min / max leaves out; None: none
        return sorted_ops[slots[f"nn:{p.name}"]] \
            if f"nn:{p.name}" in slots else None

    def reads(p):
        """The scans the table of p (and its non-null count) is read
        from; none where it is `_rows` or a segment reduce."""
        names = (f"nn:{p.name}", f"w:{p.name}") \
            if p.kind in ("min", "max") else \
            (f"{'m' if p.kind == 'count' else 'v'}:{p.name}",)
        return [n for n in names if n in prefixed or n in words]

    # the scans of the tables that are read at every slot ride `starts`'
    # sort where the caller's rule says so
    riders = {name: scan(name)
              for p in _decides(plans, top, having and having[1])
              for name in reads(p)} if program.boundary == "sorted" else {}
    with stage_scope("runs"):
        starts, rode = _run_starts(skeys, cap, list(riders.values()))
    rode = dict(zip(riders, rode))

    def before(name, row=None):
        """The scan `name` at the row before each of `row` (None: every
        one of `starts`, [cap + 1] values), 0 before row 0: what rode
        `starts`' sort, else a gather."""
        if row is None and name in rode:
            return rode[name]
        x, row = scans[name], starts if row is None else row
        return jnp.where(row > 0, x[jnp.maximum(row - 1, 0)], 0)

    def run_sum(name, acc_dtype, at=None):
        """Exact integer sum of the sorted operand `name` over the runs
        of the slots `at` (None: every slot), as `acc_dtype`: the
        inclusive prefix sum read at the run's last row less its value
        before the run's first. Wrapping arithmetic makes the difference
        exact whatever the prefix holds, so long as the run's own sum
        fits the word the prefix is taken in (`prefixed`: the
        accumulator's, or a narrowed sum's int32, which `_narrow_ok`
        answers for)."""
        scan(name)
        with stage_scope("gather"):
            if at is None:
                ends = before(name)
                total = ends[1:] - ends[:-1]
            else:
                total = before(name, starts[at + 1]) \
                    - before(name, starts[at])
            return total.astype(acc_dtype)

    def run_count(name, at=None):
        return run_sum(name, np.int32, at)

    def segment(f, v):
        # what neither gives: XLA's segment reduce, told that the ids are
        # sorted
        with stage_scope("segment"):
            return f(v, gid, num_segments=cap + 1,
                     indices_are_sorted=True)[:cap]

    def kept(t, at):
        # a [cap] table that is built whole, cut to the slots
        if at is None:
            return t
        with stage_scope("gather"):
            return t[at]

    def table(p, at=None):
        """A count's or a sum's table at the slots `at`."""
        if p.kind == "count":
            return kept(rows, at) if p.filter_fn is None else \
                run_count(f"m:{p.name}", at)
        if prefix_summed(p):
            return run_sum(f"v:{p.name}", p.acc_dtype, at)
        return kept(segment(jax.ops.segment_sum,
                            sorted_ops[slots[f"v:{p.name}"]]), at)

    # inside a non-SENTINEL run every row is unmasked: its length is its
    # row count, and a slot is present exactly where its run is not empty
    with stage_scope("gather"):
        rows = starts[1:] - starts[:-1]

    def extreme(p, rows_at, at=None):
        """A min's or a max's table at the slots `at` and its non-null
        count there (`rows_at`, the slots' row counts, where the
        aggregator leaves no row out): of a column stored in 32 bits or
        fewer the running word (`_ext_running`) at the runs' last rows,
        garbage in an empty run."""
        nn = rows_at if counted(p) is None else \
            run_count(f"nn:{p.name}", at)
        word = f"w:{p.name}"
        if word in words:
            running = scan(word)
            with stage_scope("gather"):
                ends = starts[1:] if at is None else starts[at + 1]
                v = _ext_value(
                    rode[word][1:] if at is None and word in rode else
                    running[jnp.maximum(ends - 1, 0)],
                    p.kind, words[word][2])
        else:
            v = kept(segment(
                jax.ops.segment_min if p.kind == "min" else
                jax.ops.segment_max, sorted_ops[slots[f"v:{p.name}"]]),
                at)
        # an empty slot holds the accumulator's identity, whatever
        # width the rows were reduced at
        with stage_scope("gather"):
            return jnp.where(nn > 0, v.astype(p.acc_dtype),
                            _ident(p.acc_dtype, p.kind)), nn

    # the [cap] tables a cut is decided from, built before it:
    # name -> (table, non-null count or None)
    at = live = None
    whole = {}
    if top is not None:
        from tpu_olap.kernels.topk import top_k_groups
        metric, threshold, inverted = top
        ranked = next(p for p in plans if p.name == metric)
        if ranked.kind not in ("count", "sum"):
            raise UnsupportedAggregation(
                f"no device threshold by a {ranked.kind!r}")
        whole[metric] = (table(ranked), None)
        with stage_scope("threshold"):
            at, _ = top_k_groups(whole[metric][0], rows > 0, threshold,
                                 inverted)
    elif having is not None:
        test, names, n_keep = having
        for p in plans:
            if p.name in names:
                whole[p.name] = (table(p), None) \
                    if p.kind in ("count", "sum") else extreme(p, rows)
        with stage_scope("having"):
            passing = (rows > 0) & test(whole, consts)
            n_kept = passing.sum(dtype=jnp.int32)
            # the passing slots first, in slot order: a one-operand sort,
            # as `starts` is (no scatter, and a gather a kept row)
            slot = jax.lax.sort(
                jnp.where(passing, jnp.arange(cap, dtype=jnp.int32), cap),
                is_stable=False)[:n_keep]
            live = slot < cap
            at = jnp.minimum(slot, cap - 1)

    # The key of slot g is its first row's; past the present groups that
    # row is in the SENTINEL tail, or out of bounds
    with stage_scope("gather"):
        def first_rows():
            # taken anew a table: the text a wide key's program always had
            return starts[:cap] if at is None else starts[at]
        out = {"_count": count, "_rows": kept(rows, at),
               "_keys": _key_table(skeys[0], first_rows(), 0)}
        if live is not None:
            out["_kept"] = n_kept
            out["_rows"] = jnp.where(live, out["_rows"], 0)
            out["_keys"] = jnp.where(live, out["_keys"], SENTINEL)
        for w, name in enumerate(key_names(len(kwords))[1:], 1):
            out[name] = _key_table(skeys[w], first_rows(), w)
    if narrowed:
        # |a run's sum| <= its rows x the column's largest |value|: where
        # that fits int32 for the longest run, every wrapped difference
        # above is the sum itself. A masked row rides as 0, so the plain
        # max / min over the sorted operand is the unmasked rows'
        with stage_scope("prefix"):
            longest = rows.max(initial=0).astype(jnp.int64)
            ok = jnp.ones((), bool)
            for name in narrowed:
                v = sorted_ops[slots[f"v:{name}"]]
                largest = jnp.maximum(v.max(initial=0).astype(jnp.int64),
                                     -v.min(initial=0).astype(jnp.int64))
                ok = ok & (longest * largest <= np.iinfo(np.int32).max)
            out["_narrow_ok"] = ok

    for p in plans:
        if p.name in whole:
            # a table the cut was decided from is built once: its kept
            # rows are its own
            v, nn = whole[p.name]
            out[p.name] = kept(v, at).astype(p.acc_dtype)
            if p.kind in ("min", "max"):
                out[f"_nn_{p.name}"] = out["_rows"] if nn is rows \
                    else kept(nn, at)
            continue
        if p.kind in ("count", "sum"):
            out[p.name] = table(p, at).astype(p.acc_dtype)
            continue
        if p.kind in ("min", "max"):
            out[p.name], out[f"_nn_{p.name}"] = extreme(p, out["_rows"], at)
            continue
        if p.kind == "hll":
            h = sorted_ops[slots[f"h:{p.name}"]]
            valid = sorted_ops[slots[f"hv:{p.name}"]]
            with stage_scope("segment"):
                regs = hll_mod.hll_update(h, valid,
                                          jnp.where(valid, gid, 0), cap + 1)
            out[p.name] = kept(regs[:cap], at)
            continue
        if p.kind == "theta":
            h = sorted_ops[slots[f"h:{p.name}"]]
            valid = sorted_ops[slots[f"hv:{p.name}"]]
            # theta_update routes invalid rows to the num_groups pad row
            # itself; gid==cap (overflow/sentinel) rows land in the pad
            # row and are sliced off
            with stage_scope("segment"):
                t = theta_mod.theta_update(h, valid, gid, cap + 1,
                                           p.theta_k)
            out[p.name] = kept(t[:cap], at)
            continue
    return out


def _key_table(word, first_rows, w):
    """The table of word `w` of the key: the sorted `word` at the runs'
    `first_rows`, read in the word's own width (an int32 word is ONE u32
    gather where an int64 is two) and widened to the int64 every caller
    holds. Past the present groups the row is in the sentinel tail or out
    of bounds: word 0 holds the int64 SENTINEL there, a later word 0 or
    what the tail holds (`_keys` says which slots are present)."""
    fill = _sentinel(word) if w == 0 else 0
    table = word.at[first_rows].get(mode="fill", fill_value=fill)
    if table.dtype == np.int64:
        return table
    wide = table.astype(np.int64)
    return jnp.where(table == fill, SENTINEL, wide) if w == 0 else wide


def _gathered(p) -> bool:
    # a table of its own, not read off `_rows`
    return p.kind != "count" or p.filter_fn is not None


def _segment_reduced(p, col_dtypes, cap) -> bool:
    return not prefix_summed(p) and _ext_word(p, col_dtypes, cap) is None


def _own_count(p, nullable) -> bool:
    # a min / max that leaves rows out counts the rest itself
    return p.kind in ("min", "max") and (
        p.filter_fn is not None or p.fields[0] in nullable)


def _decides(plans, top, having) -> list:
    """The plans whose [cap] tables a program builds whole: what a cut is
    decided from (`top`'s ranked metric, the names a `having` reads),
    every one where there is none."""
    if top is None and having is None:
        return list(plans)
    names = {top[0]} if top is not None else set(having)
    return [p for p in plans if p.name in names]


def cap_tables(plans, col_dtypes, cap, top=None, nullable=(),
               having=None) -> int:
    """How many [cap]-sized tables `sparse_group_reduce`'s program gathers
    or segment-reduces, from static facts alone (`sparse_reduce_form`'s,
    and `nullable`: the fields that carry a null mask). `_rows`, a
    difference of `starts`, costs no gather and is not counted, nor is
    what reads it (an unfiltered count, the non-null count of a min / max
    that leaves no row out). Without a cut every table: `_keys` and one an
    aggregate, two a min / max with a non-null count of its own. With
    `top` the ranked metric's alone, with `having` (the names its
    predicate reads) the tested aggregates', beside what still
    segment-reduces (a float sum, a 64-bit min / max, a sketch): the
    others are read at the kept rows. A table read at the boundaries
    counts whichever way it is (`boundary_read`)."""
    if top is not None or having is not None:
        decides = {p.name for p in _decides(plans, top, having)}
        return sum(1 + _own_count(p, nullable)
                   if p.name in decides and _gathered(p)
                   else int(_segment_reduced(p, col_dtypes, cap))
                   for p in plans)
    return 1 + sum(_gathered(p) + _own_count(p, nullable) for p in plans)


def boundary_read(plans, col_dtypes, cap, n, top=None, nullable=(),
                  having=None):
    """"sorted" | "gather" | None: how `sparse_group_reduce`'s program
    over `n` sorted rows reads its whole [cap] tables at the runs'
    boundaries, from `cap_tables`' static facts and `n`: as operands of
    `starts`' sort or as gathers after it (`boundary_spelling`). None
    where it reads none there: every table it builds whole is `_rows`, a
    segment reduce or (`_keys`) a read AT `starts`, which stays a
    gather."""
    whole = any(_own_count(p, nullable) or (
        _gathered(p) and not _segment_reduced(p, col_dtypes, cap))
        for p in _decides(plans, top, having))
    return boundary_spelling(n, cap) if whole else None


def program_words(program, plans, col_dtypes, nullable, sizes, key_words,
                  having=None) -> dict:
    """What the record, the `dispatch` span and EXPLAIN say of `program`,
    each word the function of that name's (`reduce_form`:
    `sparse_reduce_form`'s) of static facts alone: the plan's aggregates,
    the dtype each aggregated column is stored at (`col_dtypes`), the
    fields that carry a null mask (`nullable`), the key's id domains and
    their `pack_key_words`, and the names the plan's device HAVING reads
    (`having`, counted where the program has its bucket). A word that
    does not apply (no min / max word, no integer sum, no whole table
    read at the boundaries) is absent."""
    cap = program.cap
    words = {
        "reduce_form": sparse_reduce_form(plans, col_dtypes, cap),
        "ext_word_bits": ext_word_bits(plans, col_dtypes, cap),
        "sum_word_bits": sum_word_bits(plans, col_dtypes, program.narrow),
        "cap_tables": cap_tables(
            plans, col_dtypes, cap, program.top, nullable,
            having if program.kept is not None else None),
        "boundary_read": program.boundary,
        "key_words": len(key_words),
        "key_bits": key_bits(sizes),
        "key_sort_bits": key_sort_bits(sizes, key_words)}
    return {k: v for k, v in words.items() if v is not None}


def sparse_group_count(key, mask):
    """{"_count": [] int32}: the groups present among the unmasked rows,
    and nothing else: a sort of the key alone (one operand, or one a word
    of a wide key) and its run boundaries, no table. What the dispatch
    asks first of a group space past the budget whose count no hint tells
    (`sparse_dispatch.attempt_loop`'s `count_first`): a cap attempt that
    overflows compiles the whole multi-operand sort program only to
    learn this number."""
    words = _key_words(key)
    sentinel = _sentinel(words[0])
    with stage_scope("sort"):
        skeys = jax.lax.sort(
            (jnp.where(mask, words[0], sentinel), *words[1:]),
            num_keys=len(words), is_stable=False)
    with stage_scope("runs"):
        first = jnp.concatenate([jnp.ones((1,), bool), _changes(skeys)])
        return {"_count": (first & (skeys[0] != sentinel))
                .sum(dtype=jnp.int32)}


def compile_having(spec, plans, pool):
    """(test, names) where the device can decide the HAVING `spec` of a
    sparse group-by, else None (the host decides it over the fetched
    table, `results.eval_having`). It can where `spec` is built of
    greaterThan / lessThan / equalTo under and / or / not alone, every
    literal is a whole number an int64 holds, and every aggregate tested
    is one whose table column IS its final value, held as an integer: a
    count, a long sum, an integer min / max. A post-aggregation, a
    sketch's estimate, a float (its NaN) and a dimension's value are made
    on the host, and one of them anywhere keeps the whole HAVING there.
    `names` are the aggregates read; `test(tables, consts)` takes
    {name: ([cap] table, [cap] non-null count or None)} and gives the
    [cap] mask as the host would: a min / max over no row is null there,
    and a comparison with null is false. The literals ride the ConstPool
    (`pool`), so one program serves every literal."""
    from tpu_olap.ir import having as H

    by_name = {p.name: p for p in plans}
    names = set()
    compare = {H.GreaterThanHaving: operator.gt,
               H.LessThanHaving: operator.lt, H.EqualToHaving: operator.eq}

    def decidable(h) -> bool:
        if type(h) in compare:
            p, v = by_name.get(h.aggregation), h.value
            return p is not None and (prefix_summed(p) or (
                p.kind in ("min", "max")
                and np.dtype(p.acc_dtype).kind == "i")) \
                and not isinstance(v, bool) \
                and isinstance(v, (int, float)) \
                and abs(v) < (1 << 62) and v == int(v)
        if isinstance(h, (H.AndHaving, H.OrHaving)):
            return bool(h.having_specs) and all(
                decidable(x) for x in h.having_specs)
        return isinstance(h, H.NotHaving) and decidable(h.having_spec)

    def build(h):
        if type(h) in compare:
            op, name = compare[type(h)], h.aggregation
            c = pool.add(int(h.value), np.int64)
            names.add(name)

            def leaf(tables, consts):
                t, nn = tables[name]
                m = op(t.astype(np.int64), consts[c])
                return m if nn is None else m & (nn > 0)
            return leaf
        if isinstance(h, H.NotHaving):
            inner = build(h.having_spec)
            return lambda tables, consts: ~inner(tables, consts)
        parts = [build(x) for x in h.having_specs]
        join = operator.and_ if isinstance(h, H.AndHaving) else operator.or_
        return lambda tables, consts: functools.reduce(
            join, (f(tables, consts) for f in parts))

    if not decidable(spec):
        return None
    return build(spec), frozenset(names)


def merges_on_device(plans) -> bool:
    """Whether `merge_device` holds every aggregate of the plan: counts,
    sums, mins and maxes are [cap] tables that ride a sort beside the
    key. A sketch's [cap, m] state does not, and keeps the broker's arm
    (`merge_sparse`)."""
    return all(p.kind in ("count", "sum", "min", "max") for p in plans)


def merge_device(tables: dict, plans, parts: int):
    """Merge `parts` compact tables on the device. `tables` holds them
    laid end to end (an all_gather of the chips' first rows; an empty
    slot carries the SENTINEL key and the reduces' identities). A chip's
    table holds a key once, so after one sort by key a group is a run of
    at most `parts` rows: the run's first row takes in the `parts - 1`
    rows after it where they carry its key — `_rows`, counts, sums and
    non-null counts re-sum, min / max re-extremise — and a second sort,
    by the key on a run's first row and the SENTINEL elsewhere, leaves
    the merged groups first, in key order, as the one-chip program's
    table has them. No row is gathered or scattered. -> tables as long
    as the input, `_count` the merged groups."""
    names = [n for n in tables if n != "_keys"]
    sorted_ops = jax.lax.sort(
        (tables["_keys"],) + tuple(tables[n] for n in names),
        num_keys=1, is_stable=False)
    skey = sorted_ops[0]
    kinds = {p.name: p.kind for p in plans if p.kind in ("min", "max")}
    first = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]]) \
        & (skey != SENTINEL)

    def later(v, k, fill):
        """v as it stands k rows on, `fill` past the end."""
        return jnp.concatenate([v[k:], jnp.full((k,), fill, v.dtype)])

    merged = []
    for name, v in zip(names, sorted_ops[1:]):
        kind = kinds.get(name, "sum")
        fill = _ident(v.dtype, kind) if kind != "sum" else 0
        f = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[kind]
        acc = v
        for k in range(1, parts):
            same = later(skey, k, SENTINEL) == skey
            acc = f(acc, jnp.where(same, later(v, k, fill), fill))
        merged.append(acc)
    out = jax.lax.sort(
        (jnp.where(first, skey, SENTINEL),) + tuple(merged),
        num_keys=1, is_stable=False)
    empty = out[0] == SENTINEL
    result = {"_count": first.sum(dtype=jnp.int32), "_keys": out[0]}
    for name, v in zip(names, out[1:]):
        kind = kinds.get(name, "sum")
        result[name] = jnp.where(
            empty, _ident(v.dtype, kind) if kind != "sum" else 0, v)
    return result


def merge_sparse(parts: list, plans, cap):
    """The host broker's merge of compacted tables (the chips' present
    rows, fetched), in numpy: concatenate and re-reduce by key into [cap]
    tables. Values are already partial aggregates, so the merge semantics
    differ from row reduction — sums and counts re-sum, min/max
    re-extremize, HLL registers re-max, theta re-merges pairwise."""
    keys = np.concatenate([p["_keys"] for p in parts])
    order = np.argsort(keys, kind="stable")
    skey = keys[order]
    # run ids; gid clips into the dropped overflow+sentinel slot `cap`
    boundary = np.concatenate([np.ones((1,), bool), skey[1:] != skey[:-1]])
    gid = np.cumsum(boundary.astype(np.int32)) - 1
    count = (boundary & (skey != SENTINEL)).sum(dtype=np.int32)
    gid = np.where((gid < cap) & (skey != SENTINEL), gid, cap)
    # a chip whose LOCAL table overflowed already dropped groups; the
    # merged distinct count alone cannot see them, so take the max with
    # every per-part count — the runner then retries with a larger cap
    for p in parts:
        if "_count" in p:
            count = np.maximum(count, p["_count"].astype(np.int32))

    def gathered(name):
        return np.concatenate([p[name] for p in parts])[order]

    def seg_sum(v):
        out = np.zeros((cap + 1,) + v.shape[1:], v.dtype)
        np.add.at(out, gid, v)
        return out[:cap]

    def seg_ext(v, kind):
        out = np.full((cap + 1,) + v.shape[1:], _ident(v.dtype, kind),
                      v.dtype)
        (np.minimum if kind == "min" else np.maximum).at(out, gid, v)
        return out[:cap]

    out = {"_count": count, "_rows": seg_sum(gathered("_rows"))}
    out["_keys"] = seg_ext(skey, "min")
    for p in plans:
        if p.kind in ("count", "sum"):
            out[p.name] = seg_sum(gathered(p.name))
        elif p.kind in ("min", "max"):
            out[p.name] = seg_ext(gathered(p.name), p.kind)
            out[f"_nn_{p.name}"] = seg_sum(gathered(f"_nn_{p.name}"))
        elif p.kind == "hll":
            out[p.name] = seg_ext(gathered(p.name), "max")
        elif p.kind == "theta":
            out[p.name] = _seg_theta_union(gathered(p.name), gid, cap,
                                           len(parts))
        else:
            raise UnsupportedAggregation(p.kind)
    return out


def _seg_theta_union(rows, gid, cap, n_parts):
    """Segmented theta union, in numpy: [n, k] row-sorted tables with
    group ids `gid` (sorted; cap = dropped pad slot) -> [cap, k] merged
    tables of the k smallest distinct per group. Each part contributes at
    most one row per key, so within-group rank < n_parts; rows
    rank-scatter into a [cap, n_parts*k] wide buffer which sorts,
    dedupes, and truncates. Transient memory is cap * n_parts * k * 8B —
    sparse_theta_k_cap keeps that modest."""
    n, k = rows.shape
    idx = np.arange(n, dtype=np.int32)
    boundary = np.concatenate([np.ones((1,), bool), gid[1:] != gid[:-1]])
    seg_start = np.maximum.accumulate(np.where(boundary, idx, 0))
    rank = np.minimum(idx - seg_start, n_parts - 1)
    slot = gid.astype(np.int64) * n_parts + rank
    buf = np.full(((cap + 1) * n_parts, k), theta_mod.EMPTY, rows.dtype)
    buf[slot] = rows
    wide = buf[:cap * n_parts].reshape(cap, n_parts * k)
    wide = np.sort(wide, axis=-1)
    dup = np.concatenate(
        [np.zeros((cap, 1), bool), wide[:, 1:] == wide[:, :-1]], axis=-1)
    wide = np.sort(np.where(dup, theta_mod.EMPTY, wide), axis=-1)
    return wide[:, :k]
