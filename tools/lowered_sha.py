"""sha-256 of the lowered text of the sparse programs the benchmark's cells
run (q3, q10, `q10p`, Q18, `q18p`, the three Druid TopNs: the count program,
the narrow and the wide table programs at two caps; the mesh's sort and merge
programs), on the tree given as argv[1], lowered here on the CPU at `ROWS`
rows: what a PR that says "the other cells' programs are the parent's text"
compares (PRs 41-43). Each table program is the dispatch's own choice for
the rows and the cap (`sparse_dispatch.choose_program`, made by
`make_sparse_kernel(program)`; the wide program beside the narrow one by
`dataclasses.replace`): at these rows and caps every program is past 8 rows
a slot, the `gather` side. On a tree before PR 45 use that tree's copy of
this tool.

A key's width moves a program (PR 44: `sparse_groupby.key_word_dtypes`, a
word whose ids fit 31 bits rides as int32): the three Druid TopNs, their
count program and `q10p` are the accepted cells' programs it moves, and no
other. At these rows every domain is narrower than its cell's, so each
template is lowered on the path its cell takes: `CELL_SORT_BITS` (what the
cells' records say, `key_sort_bits`) stands in for the rule wherever the
small plan has as many words as the cell's, and the tag ends in those bits
(`proxy-bits`, with the rule's own answer, where it has not: `q10p` is one
word under 2^62 here). `key-shape:*` are the programs of the same key
builder and reduce over the CELLS' OWN domains (`CELL_SIZES`, the published
SF10's), with no dataset: there the rule answers for itself on either tree.

    python tools/lowered_sha.py . > /root/scratch/change.json
    python tools/lowered_sha.py /root/scratch/parent > /root/scratch/parent.json
"""
import dataclasses
import hashlib
import json
import os
import sys
import tempfile

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
os.chdir(root)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

ROWS = 60000
ROWS_OF = {"druid-lineitem-sf100-chip": 600000}
CELLS = {"tpch-flat-sf10-chip": ["q3", "q10"],
         "druid-lineitem-sf100-chip": [
             "top_100_parts", "top_100_parts_details",
             "top_100_parts_filter"],
         "tpch-flat-sf10-having-chip": ["q18"],
         "tpch-flat-sf10-widekey-chip": ["q10p", "q18p"],
         "tpch-flat-sf10-mesh4": ["q3", "q10"]}


# template -> the id domains of its cell's group key in radix order, the
# time bucket first (the published SF10: 15M orders on sparse keys, 1.5M
# customers of whom a million have orders, 2M parts, 2,406 order dates, 25
# nations; `c_acctbal` and `o_totalprice` in cents)
CELL_SIZES = {"q3": (1, 59_999_969, 2406, 1),
              "q10": (1, 1_499_999, 1_000_000, 25),
              "q18": (1, 1_499_999, 59_999_969, 2406),
              "q10p": (1, 1_499_999, 1_000_000, 1_099_999, 25),
              "q18p": (1, 1_000_000, 1_499_999, 59_999_969, 2406,
                       55_000_000),
              "top_100_parts": (1, 2_000_000)}
# template -> `key_sort_bits` on its cell's records
CELL_SORT_BITS = {"q3": [64], "q10": [64], "q18": [64], "q10p": [64, 32],
                  "q18p": [64, 64], "top_100_parts": [32],
                  "top_100_parts_details": [32],
                  "top_100_parts_filter": [32]}


def sha(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def uncut_min_max(sg):
    """A GroupBy with no cut whose int8 min and int32 max words are read
    whole, beside a sum and the row count: no cell's template, so a plan
    of its own (60 rows a slot)."""
    import jax.numpy as jnp
    import numpy as np

    from tpu_olap.kernels.groupby import AggPlan
    i64 = np.dtype(np.int64)
    plans = [AggPlan("lo", "min", ("d",), i64), AggPlan("n", "count", (), i64),
             AggPlan("hi", "max", ("e",), i64),
             AggPlan("s", "sum", ("d",), i64)]
    env = {"cols": {"d": jnp.zeros(ROWS, jnp.int8),
                    "e": jnp.zeros(ROWS, jnp.int32)}, "nulls": {}}
    return {f"uncut-min-max:{'narrow' if narrow else 'wide'}": sha(jax.jit(
        lambda k, m, e: sg.sparse_group_reduce(
            k, m, e, plans, {}, sg.SparseProgram(1024, narrow=narrow))).lower(
        jnp.zeros(ROWS, jnp.int64), jnp.ones(ROWS, bool), env))
        for narrow in (False, True)}


def key_shapes(sg):
    """The count program and a table program (an int64 sum of an int32
    column and the row count, 1,024 slots) over a key built from zero ids
    of `CELL_SIZES`' domains, as `make_sparse_kernel` builds it."""
    import jax.numpy as jnp
    import numpy as np

    from tpu_olap.kernels.groupby import AggPlan
    plans = [AggPlan("s", "sum", ("v",), np.dtype(np.int64)),
             AggPlan("n", "count", (), np.dtype(np.int64))]
    env = {"cols": {"v": jnp.zeros(ROWS, jnp.int32)}, "nulls": {}}
    mask = jnp.ones(ROWS, bool)
    out = {}
    for name, sizes in CELL_SIZES.items():
        sizes = sizes[1:]     # granularity "all": the bucket carries no id
        words = sg.pack_key_words(sizes)
        ids = [jnp.zeros(ROWS, jnp.int32) for _ in sizes]

        def key(ids):
            return sg.build_group_key64(ids, sizes, words=words)[0]
        out[f"key-shape:{name}:words{len(words)}:count"] = sha(jax.jit(
            lambda ids, m: sg.sparse_group_count(key(ids), m))
            .lower(ids, mask))
        out[f"key-shape:{name}:words{len(words)}:table"] = sha(jax.jit(
            lambda ids, m, e: sg.sparse_group_reduce(
                key(ids), m, e, plans, {}, sg.SparseProgram(1024)))
            .lower(ids, mask, env))
        out[f"key-shape:{name}:rule-bits"] = sg.key_sort_bits(sizes, words)
    return out


def on_the_cells_path(sg, rule, name, phys):
    """Make `sg.key_word_dtypes` (`rule`, the tree's own) answer with the
    widths template `name`'s cell sorts at where the small plan has as
    many words, and -> the tag's tail."""
    import numpy as np
    own = [8 * d.itemsize for d in rule(phys.sizes, phys.key_words)]
    real = CELL_SORT_BITS[name]
    if len(own) != len(real):
        sg.key_word_dtypes = rule
        return f":proxy-bits{own}"
    sg.key_word_dtypes = lambda sizes, words: tuple(
        np.dtype(f"int{b}") for b in real)
    return ""


def main():
    import importlib

    from tpu_olap import Engine
    from tpu_olap.executor import EngineConfig, sparse_dispatch
    from tpu_olap.executor import sharding as sh
    from tpu_olap.kernels import sparse_groupby as sg
    out = {}
    rule = sg.key_word_dtypes
    for cfg_name, names in CELLS.items():
        with open(f"perfbench/configs/{cfg_name}.json") as f:
            cfg = json.load(f)
        ds = importlib.import_module(f"perfbench.datasets.{cfg['dataset']}")
        tmp = tempfile.mkdtemp(prefix="sha_")
        rows = ROWS_OF.get(cfg_name, ROWS)
        data = ds.generate(rows, 7, tmp, 1)
        eng = Engine(EngineConfig(**cfg["engine_config"]))
        ds.register(eng, data["paths"], rows, 7)
        r = eng.runner
        templates = ds.templates()
        for name in names:
            plan = eng.planner.plan(templates[name])
            phys = r._lower_cached(plan.query, plan.entry.segments)
            assert phys.sparse, name
            env, valid, seg_mask = r._prepare(phys, {})
            n = int(valid.size)
            consts_dev, seg_arg = r._args_for(phys, seg_mask, r.mesh)
            tag = f"{cfg_name}:{name}"
            tail = on_the_cells_path(sg, rule, name, phys)
            win = r._segment_window(phys, len(seg_mask))
            mesh = r.mesh
            stored = {c: a.dtype for c, a in env["cols"].items()}
            rows = ((win[1] * valid.shape[1]) if win else n) \
                // (mesh.devices.size if mesh else 1)

            def chosen(cap):
                return sparse_dispatch.choose_program(
                    r, phys, stored, frozenset(env["nulls"]), rows, cap,
                    window=win[1] if win else None)

            if mesh is not None:
                for cap in (1024,):
                    sort = sh.mesh_sparse_kernel(phys, mesh, chosen(cap))
                    out[f"{tag}:mesh-sort:{cap}"] = sha(
                        sort.lower(env, valid, seg_arg, consts_dev))
                    tables = jax.eval_shape(sort, env, valid, seg_arg,
                                            consts_dev)
                    tables = {k: v for k, v in tables.items()
                              if k != "_count"}
                    out[f"{tag}:mesh-merge:{cap}"] = sha(
                        sh.mesh_merge_kernel(phys, mesh, 256).lower(tables))
                continue

            def lower(program):
                return sparse_dispatch.jit_whole(r, phys, program).lower(
                    env, valid, seg_arg, consts_dev,
                    *([win[0]] if win else []))

            out[f"{tag}:count{tail}"] = sha(lower(chosen(None)))
            for cap in (1024, 4096):
                program = chosen(cap)
                for narrow in (False, True):
                    out[f"{tag}:cap{cap}:{'narrow' if narrow else 'wide'}"
                        f":n{n}:win{win}:top{bool(program.top)}"
                        f":kept{program.kept}{tail}"] = sha(lower(
                            dataclasses.replace(program, narrow=narrow)))
        eng.close()
    sg.key_word_dtypes = rule
    out.update(uncut_min_max(sg))
    out.update(key_shapes(sg))
    json.dump(out, sys.stdout, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
