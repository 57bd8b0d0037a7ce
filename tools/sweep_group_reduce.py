"""The dense group reduce on the chip: XLA's scatter against the
compare-and-reduce form (`kernels/groupby.py::_cmp_reduce`, a loop over row
blocks) and against its one-reduce spelling with no loop (`bcast`: the
[K, N] compare-select as one reduce's input, which XLA:CPU materializes),
over the number of slots K. One row per (rows, dtype, K, form): compile
seconds, temporary bytes, the median of `--reps` warm runs in ms, and
whether the table equals numpy's. `COMPARE_MAX_GROUPS` in
`kernels/groupby.py` is set from this table (PERF.md section 6, PR 28): the
largest K of the issue's grid (2 to 2,048) at which the compare form is at
least twice as fast as the scatter at both sizes and compiles in seconds;
the K past it are there to see where the two forms cross. The form
`sparse_topn` is what `executor/lowering.py::topn_takes_sparse` sends a
TopN to where the dense plan would be the scatter: the engine's sparse
reduce and the threshold on the device (PERF.md section 6, PR 32), which
ranks first and reads the tables it does not rank at the 100 kept rows
(PR 39). `--tables N` puts N more integer sums beside the ranked one, and
`sparse_topn_tables_first` is the spelling before PR 39 (every [K] table,
then the ranking, then the cut): at one N the two differ by N + 1 K-sized
gathers (the keys' and each table's) against as many kept-row reads.
`--dtypes int8` sums a column stored as int8 (1-50, TPC-H's quantity) into
int64, and `--sum-word 32 64` runs the sparse forms as the engine's narrow
program (every such sum one int32 sort operand, an int32 prefix tree, one
gather a boundary, and `_narrow_ok`, which the row reports) and as its wide
one (two u32 of each): over rows, K and `--tables` the two differ by one
sort operand, the prefix tree's width and a gather a sum (PERF.md section 6,
PR 41). `--key-words 1 2 3` gives the sparse forms' key that many int64
words (each further word a function of the first, so the groups and their
order stay): the sort compares them in turn, a run ends where any changes
and each is one more table read where the keys are; over rows and K the
price of a key word stands beside a sum's (PERF.md section 6, PR 42).
`--boundary-read gather sorted` runs the sparse forms with the ranked sum's
prefix read at the runs' boundaries by a gather after `starts`' sort and as
a second operand of it, whatever `sparse_groupby.boundary_spelling` would
choose (`rule`, the default, leaves it the choice; the row's `boundary` says
what ran): over `--ks` at fixed rows the two cross where
`BOUNDARY_SORT_MAX_ROWS_PER_SLOT` rests (PERF.md section 6, PR 43).
`--key-bits 32 64` hands the sparse forms' key (k slots: ids under 2^31) as
an int32 word or an int64 one, as `sparse_groupby.key_word_dtypes` would
of a domain that fits 31 bits and of one that does not; under
`--key-words 2` the width is the SECOND word's (5 bits of the first, as
`q10p`'s `c_nation`; word 0 stays int64): a sort whose key words differ in
width. Over rows at one k the rows differ by one u32 sort operand that is
also compared, the boundary test's bytes and a gather of the keys' table
(PERF.md section 6, PR 44).

    python tools/sweep_group_reduce.py --rows 59986052 --ks 2000001 \
        --dtypes int64 --tables 0 3 \
        --forms sparse_topn sparse_topn_tables_first

    python tools/sweep_group_reduce.py --rows 59986052 \
        --ks 2000001 16777216 --dtypes int8 --tables 0 1 \
        --sum-word 32 64 --forms sparse_topn

    python tools/sweep_group_reduce.py --rows 59986052 --ks 2000001 \
        --dtypes int8 --tables 0 1 --sum-word 32 64 --key-words 1 2 3 \
        --forms sparse_topn

    python tools/sweep_group_reduce.py --rows 60030976 \
        --ks 262144 2000001 4194304 16777216 --dtypes int8 --sum-word 32 \
        --boundary-read gather sorted --forms sparse_topn

    python tools/sweep_group_reduce.py --rows 59986052 --ks 2000001 \
        --dtypes int8 --tables 0 1 --sum-word 32 --key-bits 32 64 \
        --key-words 1 2 --forms sparse_topn

    python tools/sweep_group_reduce.py                  # on the chip
    python tools/sweep_group_reduce.py --compile-only   # here, for a
        described v5e: compile seconds and temporary bytes, nothing runs
    python tools/sweep_group_reduce.py --allow-cpu --rows 200000   # rehearsal

`--sorted` sweeps the sparse group-by's reduce instead
(`kernels/sparse_groupby.py`, PERF.md section 6, PRs 30 and 37): group ids
sorted into `--runs` runs with a masked tail, as they leave the sort, for
an int64 sum, an int32 count, an int64 min, a float64 sum and the min / max
of a column stored as int8 or int32 (carried as int32, as the engine's sort
carries it); `segment_*` as XLA's scatter has it, the same with
`indices_are_sorted=True`, and the boundary form (an integer sum is a
difference of prefix sums at the runs' first rows; a min or a float sum a
segmented running reduce read at their last) with each of three ways to the
runs' first rows: a binary search of the slot numbers in the ids, a
one-operand sort of the boundary positions, a scatter-min of the positions.
For a stored integer min / max, PR 37's forms: `cummax_word32` and
`cummax_word64`, the running maximum of the one word (run id << b) | code
read at the runs' last rows (what the engine ships, its word width forced
here); and, as a comparison only, the value as a second sort key
(`sort_key2`: a two-operand sort by (id, value), the min at a run's first
row and the max at its last) beside what that sort costs with one key and
the word read after it (`sort_key1`).

    python tools/sweep_group_reduce.py --sorted --rows 59986052 \
        --runs 2000000 --aggs int8_min int8_max int32_min \
        --sorted-forms segment_sorted cummax_word32 cummax_word64

Prints one JSON line a row and writes them to `--out`.
"""

import argparse
import functools
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_enable_x64", True)

from tpu_olap.kernels import groupby, sparse_groupby  # noqa: E402

KS = (2, 8, 32, 128, 512, 2048, 4096, 8192, 16384)
ROWS = (36_000_000, 6_000_000)
BLOCK = 65_536   # the engine's rows a segment block: N is whole blocks


def _scatter(v, key, k):
    return jax.ops.segment_sum(v, key, num_segments=k)


def _compare(v, key, k):
    return groupby._cmp_reduce(v, key, k, 0, jnp.add,
                               functools.partial(jnp.sum, dtype=v.dtype))


def _bcast(v, key, k):
    slots = jnp.arange(k, dtype=key.dtype)
    return jnp.sum(jnp.where(key[None, :] == slots[:, None], v[None, :], 0),
                   axis=1, dtype=v.dtype)


BOUNDARY_READS = ("rule", "gather", "sorted")


def _sparse_topn(v, key, k, tables=0, rank_first=True, sum_word=64,
                 key_words=1, boundary=None, key_bits=64):
    """The other side of `lowering.topn_takes_sparse`: the engine's own
    sparse reduce into a compact table of k slots (one sort whose cost
    does not depend on k, the tables read at the runs' boundaries) with
    the TopN's threshold on the device: the sum of v, which ranks, and
    `tables` more integer sums beside it, each a sort operand, a prefix
    sum and a table of its own. `rank_first` is the engine's program
    (`sparse_group_reduce`'s `top`): of the [k] tables the ranked one
    alone is gathered, the others are read at the 100 kept rows. Without
    it, the spelling that stood until PR 39, kept here to price a
    k-sized gather against a kept-row read: every [k] table, then
    `top_k`, then each table cut to the kept slots. `sum_word` 32 asks
    for the narrow program: a sum of a column stored in 32 bits or fewer
    rides as one int32 word. `key_words` past 1 hands the key as that many
    int64 words, the further ones functions of the first: the same groups
    in the same order, sorted by every word. `key_bits` 32 hands the key
    as an int32 word; with two words the second, then the first's low 5
    bits, and word 0 stays int64. `boundary` is the `SparseProgram`'s
    field of that name (the dispatch's `boundary_read`). Returns
    the ranked sum at the kept rows, their keys, the other tables' kept rows and, of a narrow
    program, `_narrow_ok` last."""
    from tpu_olap.kernels.sparse_groupby import (SENTINEL, SparseProgram,
                                                 sparse_group_reduce)
    from tpu_olap.kernels.topk import top_k_groups
    cols = {"v": v, **{f"t{i}": v ^ (i + 1) for i in range(tables)}}
    # a column stored in fewer than 32 bits is summed into an int64
    acc = v.dtype if v.dtype.itemsize >= 4 else np.dtype(np.int64)
    plans = [groupby.AggPlan(c, "sum", (c,), acc) for c in cols]
    top = ("v", 100, False)
    narrow_key = key_bits == 32
    key = key.astype(jnp.int32 if narrow_key and key_words == 1
                     else jnp.int64)
    if key_words > 1:
        key = (key,) + tuple(
            (key & 31).astype(jnp.int32) if narrow_key and w == 1
            else (key << 31) + (key ^ (w * 0x55555))
            for w in range(1, key_words))
    out = sparse_group_reduce(
        key, jnp.ones(v.shape, bool), {"cols": cols, "nulls": {}}, plans,
        {}, SparseProgram(k, top if rank_first else None,
                          narrow=sum_word == 32, boundary=boundary))
    ok = (out.pop("_narrow_ok"),) if "_narrow_ok" in out else ()
    if not rank_first:
        order, _ = top_k_groups(out["v"], out["_keys"] != SENTINEL,
                                *top[1:])
        out = {name: t if name == "_count" else t[order]
               for name, t in out.items()}
    return (out["v"], out["_keys"]) \
        + tuple(out[c] for c in cols if c != "v") + ok


FORMS = {"scatter": _scatter, "compare": _compare, "bcast": _bcast,
         "sparse_topn": _sparse_topn,
         "sparse_topn_tables_first": functools.partial(_sparse_topn,
                                                       rank_first=False)}
# the forms `--tables` multiplies
SPARSE_FORMS = ("sparse_topn", "sparse_topn_tables_first")


def _inputs(n, dtype, k, seed=7):
    rng = np.random.default_rng(seed)
    key = rng.integers(0, k, n, dtype=np.int32)
    if dtype == "int64":   # a sum whose rows pass int32
        return rng.integers(0, 1 << 40, n, dtype=np.int64), key
    if dtype == "int8":    # a quantity of 1 to 50, stored as it is
        return rng.integers(1, 51, n, dtype=np.int8), key
    return (rng.random(n) < 0.5).astype(np.int32), key   # a filtered count


RUNS = tuple(1 << e for e in range(10, 22))
SORTED_ROWS = (36_000_000, 6_030_000)
# name: (dtype the rows are carried at, kind, dtype the column is stored at)
AGGS = {"int64_sum": ("int64", "sum", "int64"),
        "int32_count": ("int32", "sum", "int32"),
        "int64_min": ("int64", "min", "int64"),
        "float64_sum": ("float64", "sum", "float64"),
        "int8_min": ("int32", "min", "int8"),
        "int8_max": ("int32", "max", "int8"),
        "int32_min": ("int32", "min", "int32"),
        "int32_max": ("int32", "max", "int32")}


def _sorted_inputs(n, agg, runs, seed=7):
    """(v, gid, cap): ids sorted into about 0.9 * runs runs over the first
    98% of the rows, the rest the masked tail (gid == cap, v the
    aggregate's identity), as `_sorted_segments` hands them on."""
    rng = np.random.default_rng(seed)
    dtype, kind, stored = AGGS[agg]
    cap = runs
    n_valid = n - n // 50
    boundary = rng.random(n_valid) < 0.9 * runs / n_valid
    boundary[0] = True
    gid = np.full(n, cap, np.int32)
    gid[:n_valid] = np.minimum(np.cumsum(boundary, dtype=np.int32) - 1, cap)
    if dtype == "int64":    # rows that pass int32, of either sign
        v = rng.integers(-(1 << 40), 1 << 40, n, dtype=np.int64)
    elif kind != "sum":     # the stored width's whole range, both ends
        lim = np.iinfo(stored)
        v = rng.integers(lim.min, lim.max, n, dtype=np.int32, endpoint=True)
    elif agg == "int32_count":
        v = (rng.random(n) < 0.5).astype(np.int32)
    else:
        v = np.round(rng.random(n) * 1e4, 2)
    v[n_valid:] = groupby._ident(np.dtype(dtype), kind) if kind != "sum" \
        else 0
    return v, gid, cap


def _sorted_reference(v, gid, cap, kind):
    """numpy's answer by `reduceat` over the runs' first rows."""
    first = np.flatnonzero(np.concatenate([[True], gid[1:] != gid[:-1]]))
    op = {"sum": np.add, "min": np.minimum, "max": np.maximum}[kind]
    want = np.full(cap + 1, groupby._ident(v.dtype, kind)
                   if kind != "sum" else 0, v.dtype)
    want[gid[first]] = op.reduceat(v, first)
    return want[:cap]


def _starts_search(gid, cap):
    return jnp.searchsorted(gid, jnp.arange(cap + 1, dtype=gid.dtype),
                            side="left").astype(jnp.int32)


def _first_rows(gid):
    n = gid.shape[0]
    boundary = jnp.concatenate([jnp.ones((1,), bool), gid[1:] != gid[:-1]])
    return boundary, jnp.arange(n, dtype=jnp.int32)


def _starts_sort(gid, cap):
    # the boundary positions sorted to the front; the tail's own first row
    # is the last of them, and fills every slot past the present runs
    boundary, iota = _first_rows(gid)
    n_valid = jnp.sum(gid < cap, dtype=jnp.int32)
    pos = jax.lax.sort(jnp.where(boundary, iota, n_valid), is_stable=False)
    return jnp.minimum(pos[:cap + 1], n_valid)


def _starts_scatter(gid, cap):
    _, iota = _first_rows(gid)
    n_valid = jnp.sum(gid < cap, dtype=jnp.int32)
    return jnp.full((cap + 1,), n_valid, jnp.int32).at[gid].min(
        iota, indices_are_sorted=True)


def _segment(v, gid, cap, kind, is_sorted):
    f = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
         "max": jax.ops.segment_max}[kind]
    return f(v, gid, num_segments=cap + 1, indices_are_sorted=is_sorted)[:cap]


def _boundary(v, gid, cap, kind, starts_fn):
    starts = starts_fn(gid, cap)
    if kind == "sum" and jnp.issubdtype(v.dtype, jnp.integer):
        prefix = jnp.cumsum(v)
        before = jnp.where(starts > 0, prefix[jnp.maximum(starts - 1, 0)], 0)
        return before[1:] - before[:-1]
    # a segmented running reduce, read at each run's last row
    op = jnp.add if kind == "sum" else jnp.minimum
    boundary, _ = _first_rows(gid)

    def combine(a, b):
        return a[0] | b[0], jnp.where(b[0], b[1], op(a[1], b[1]))
    _, running = jax.lax.associative_scan(combine, (boundary, v))
    ident = groupby._ident(v.dtype, kind) if kind == "min" else 0
    return jnp.where(starts[1:] > starts[:-1],
                     running[jnp.maximum(starts[1:] - 1, 0)], ident)


class WordTooNarrow(ValueError):
    """The run id over the code does not fit the word asked for."""


def _cummax_word(v, gid, cap, kind, stored, word):
    """`sparse_group_reduce`'s read of a stored integer min / max (its
    `_ext_running` and `_ext_value`), the word's width forced: the running
    maximum of (run id << b) | code, read at each run's last row."""
    b = 8 * np.dtype(stored).itemsize + 1
    if cap.bit_length() + b > 8 * np.dtype(word).itemsize - 1:
        raise WordTooNarrow(f"{cap.bit_length()} bits of run id over {b} "
                            f"of code do not fit an {word} word")
    starts = _starts_sort(gid, cap)
    running = sparse_groupby._ext_running(v, None, gid, kind, stored,
                                          np.dtype(word))
    table = sparse_groupby._ext_value(
        running[jnp.maximum(starts[1:] - 1, 0)], kind, stored)
    return jnp.where(starts[1:] > starts[:-1], table.astype(jnp.int32),
                     groupby._ident(np.dtype(np.int32), kind))


def _sort_then_read(v, gid, cap, kind, stored, num_keys):
    """The whole of either way from rows to table, the sort included (the
    sweep's ids are sorted already; the sort's cost does not depend on
    that). Two keys: the value is the second sort key, so a run's min is
    at its first row and its max at its last, and nothing runs along the
    rows after the sort. One key: the engine's sort, the word read after
    it."""
    gid, v = jax.lax.sort((gid, v), num_keys=num_keys, is_stable=False)
    if num_keys == 1:
        return _cummax_word(v, gid, cap, kind, stored, "int32")
    starts = _starts_sort(gid, cap)
    at = starts[:-1] if kind == "min" else jnp.maximum(starts[1:] - 1, 0)
    return jnp.where(starts[1:] > starts[:-1], v[at],
                     groupby._ident(v.dtype, kind))


# the forms of a stored integer min / max alone: they take the column's
# stored dtype
STORED_FORMS = {
    "cummax_word32": functools.partial(_cummax_word, word="int32"),
    "cummax_word64": functools.partial(_cummax_word, word="int64"),
    "sort_key1": functools.partial(_sort_then_read, num_keys=1),
    "sort_key2": functools.partial(_sort_then_read, num_keys=2),
}
SORTED_FORMS = {
    "segment": functools.partial(_segment, is_sorted=False),
    "segment_sorted": functools.partial(_segment, is_sorted=True),
    "boundary_search": functools.partial(_boundary, starts_fn=_starts_search),
    "boundary_sort": functools.partial(_boundary, starts_fn=_starts_sort),
    "boundary_scatter": functools.partial(_boundary,
                                          starts_fn=_starts_scatter),
    **STORED_FORMS,
}


def _measure(fn, spec, inputs, want, reps, rec):
    """Compile `fn` for `spec`; with `inputs`, run it: the median of `reps`
    warm runs and whether the table is `want` (floats: to 1e-9)."""
    t0 = time.perf_counter()
    try:
        compiled = jax.jit(fn).lower(*spec).compile()
    except WordTooNarrow as e:
        rec["skipped"] = str(e)
        print(json.dumps(rec), flush=True)
        return rec
    rec["compile_s"] = round(time.perf_counter() - t0, 2)
    ma = compiled.memory_analysis()
    if ma is not None:
        rec["temp_bytes"] = int(ma.temp_size_in_bytes)
    if inputs is not None:
        got = jax.block_until_ready(compiled(*inputs))
        ms = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*inputs))
            ms.append((time.perf_counter() - t0) * 1e3)
        rec["ms"] = round(statistics.median(ms), 3)
        rec["ns_per_row"] = round(rec["ms"] * 1e6 / rec["rows"], 3)
        if isinstance(got, tuple):
            # (the ranked sum at the rows a top-100 keeps, their keys,
            # ...): by (value descending, key ascending), as the engine's
            # TopN cuts a tie
            if rec.get("sum_word") == 32 and got[-1].ndim == 0:
                rec["narrow_ok"] = bool(got[-1])
            got, top = got[:2]
            kept = np.lexsort((np.arange(len(want)), -want))[:len(top)]
            rec["top_equal"] = bool(np.array_equal(np.asarray(top), kept))
            want = want[kept]
        got = np.asarray(got)
        rec["equal"] = bool(
            np.array_equal(got, want) if got.dtype.kind != "f"
            else np.allclose(got, want, rtol=1e-9, atol=0))
    print(json.dumps(rec), flush=True)
    return rec


def sweep_sorted(args, sharding):
    out = []
    for n in args.rows or SORTED_ROWS:
        for agg in args.aggs:
            dtype, kind, stored = AGGS[agg]
            for runs in args.runs:
                inputs = want = None
                cap = runs
                if not args.compile_only:
                    v_np, gid_np, cap = _sorted_inputs(n, agg, runs)
                    want = _sorted_reference(v_np, gid_np, cap, kind)
                    inputs = (jnp.asarray(v_np), jnp.asarray(gid_np))
                spec = [jax.ShapeDtypeStruct((n,), np.dtype(d),
                                             sharding=sharding)
                        for d in (dtype, "int32")]
                for name in args.sorted_forms:
                    if name in STORED_FORMS and (
                            kind == "sum" or dtype != "int32"):
                        continue   # a stored integer min / max alone
                    fn = functools.partial(
                        SORTED_FORMS[name], cap=cap, kind=kind,
                        **({"stored": stored} if name in STORED_FORMS
                           else {}))
                    out.append(_measure(
                        fn, spec, inputs, want, args.reps,
                        dict(rows=n, agg=agg, runs=runs, form=name)))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sorted", action="store_true",
                    help="the sparse group-by's reduce over sorted runs")
    ap.add_argument("--runs", type=int, nargs="*", default=list(RUNS))
    ap.add_argument("--aggs", nargs="*", default=list(AGGS),
                    choices=list(AGGS))
    ap.add_argument("--sorted-forms", nargs="*", default=list(SORTED_FORMS),
                    choices=list(SORTED_FORMS))
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--ks", type=int, nargs="*", default=list(KS))
    ap.add_argument("--rows", type=int, nargs="*", default=None)
    ap.add_argument("--forms", nargs="*", default=list(FORMS),
                    choices=list(FORMS))
    ap.add_argument("--dtypes", nargs="*", default=["int64", "int32"],
                    choices=["int64", "int32", "int8"])
    ap.add_argument("--sum-word", type=int, nargs="*", default=[64],
                    choices=[32, 64],
                    help="the sparse TopN forms' program: 32 the narrow "
                         "one (a sum of a column stored in 32 bits or "
                         "fewer rides as one int32 word), 64 the wide")
    ap.add_argument("--key-words", type=int, nargs="*", default=[1],
                    help="the sparse TopN forms' key as this many int64 "
                         "words: sort keys, boundary tests and key tables")
    ap.add_argument("--key-bits", type=int, nargs="*", default=[64],
                    choices=[32, 64],
                    help="the sparse TopN forms' key word as int32 or "
                         "int64 (with --key-words 2: the second word)")
    ap.add_argument("--boundary-read", nargs="*", default=["rule"],
                    choices=BOUNDARY_READS,
                    help="the sparse TopN forms' read of the ranked sum's "
                         "prefix at the runs' boundaries: a gather after "
                         "`starts`' sort, a second operand of it, or what "
                         "the engine's rule picks from rows and k")
    ap.add_argument("--tables", type=int, nargs="*", default=[0],
                    help="the sparse TopN forms' integer sums beside the "
                         "ranked one: a boundary table each")
    ap.add_argument("--block-bytes", type=int, nargs="*",
                    default=[groupby._CMP_BLOCK_BYTES],
                    help="the compare form's row block, to sweep it")
    ap.add_argument("--out", default="chiprun_out/sweep_group_reduce.json")
    args = ap.parse_args()

    sharding = None
    if args.compile_only:
        # libtpu logs under the fixed /tmp/tpu_logs unless told where
        os.environ.setdefault("TPU_LOG_DIR",
                              tempfile.mkdtemp(prefix="tpu_logs_"))
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    else:
        dev = jax.devices()[0]
        if dev.platform != "tpu" and not args.allow_cpu:
            sys.exit(f"no chip: {dev.platform}")
        print(json.dumps({"device": dev.device_kind}), flush=True)

    if args.sorted:
        out = sweep_sorted(args, sharding)
    else:
        out = sweep_dense(args, sharding)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


def sweep_dense(args, sharding):
    out = []
    for n in args.rows or ROWS:
        n = -(-n // BLOCK) * BLOCK
        for dtype in args.dtypes:
            for k in args.ks:
                inputs = want = None
                if not args.compile_only:
                    v_np, key_np = _inputs(n, dtype, k)
                    inputs = (jnp.asarray(v_np), jnp.asarray(key_np))
                    want = np.zeros(k, np.int64 if dtype == "int8"
                                    else v_np.dtype)
                    np.add.at(want, key_np, v_np)
                spec = [jax.ShapeDtypeStruct((n,), np.dtype(d),
                                             sharding=sharding)
                        for d in (dtype, "int32")]
                for name, block, tables, word, words, read, kbits in [
                        (f, b, t, w, kw, br, kb) for f in args.forms
                        for b in (args.block_bytes
                                  if f == "compare" else [0])
                        for t in (args.tables
                                  if f in SPARSE_FORMS else [None])
                        for w in (args.sum_word
                                  if f in SPARSE_FORMS else [None])
                        for kw in (args.key_words
                                   if f in SPARSE_FORMS else [None])
                        for br in (args.boundary_read
                                   if f in SPARSE_FORMS else [None])
                        for kb in (args.key_bits
                                   if f in SPARSE_FORMS else [None])]:
                    if dtype == "int8" and name not in SPARSE_FORMS:
                        continue   # the dense forms sum at v's own width
                    groupby._CMP_BLOCK_BYTES = block or \
                        groupby._CMP_BLOCK_BYTES
                    more = {} if tables is None else {
                        "tables": tables, "sum_word": word,
                        "key_words": words, "key_bits": kbits}
                    if read is not None:
                        more["boundary"] = read if read != "rule" else \
                            sparse_groupby.boundary_spelling(n, k)
                    out.append(_measure(
                        functools.partial(FORMS[name], k=k, **more), spec,
                        inputs, want, args.reps,
                        dict(rows=n, dtype=dtype, k=k, form=name, **more,
                             **({"block_bytes": block} if block else {}))))
    return out


if __name__ == "__main__":
    main()
