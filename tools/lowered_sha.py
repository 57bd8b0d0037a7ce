"""sha-256 of the lowered text of the sparse programs the benchmark's cells
run (q3, q10, `q10p`, Q18, `q18p`, the three Druid TopNs: the count program,
the narrow and the wide table programs at two caps; the mesh's sort and merge
programs), on the tree given as argv[1], lowered here on the CPU at `ROWS`
rows: what a PR that says "the other cells' programs are the parent's text"
compares (PRs 41-43). Each table program is built as the runner builds it:
where the tree has `sparse_groupby.boundary_read` its answer for the rows
and the cap is passed on (at these rows and caps every program is past 8
rows a slot: the `gather` side).

    python tools/lowered_sha.py . > /root/scratch/change.json
    python tools/lowered_sha.py /root/scratch/parent > /root/scratch/parent.json
"""
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
         "druid-lineitem-sf100-chip": ["top_100_parts", "top_100_parts_details",
                                       "top_100_parts_filter"],
         "tpch-flat-sf10-having-chip": ["q18"],
         "tpch-flat-sf10-widekey-chip": ["q10p", "q18p"],
         "tpch-flat-sf10-mesh4": ["q3", "q10"]}


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
            k, m, e, plans, 1024, {}, jnp, None, None, narrow)).lower(
        jnp.zeros(ROWS, jnp.int64), jnp.ones(ROWS, bool), env))
        for narrow in (False, True)}


def main():
    import importlib

    from tpu_olap import Engine
    from tpu_olap.executor import EngineConfig
    from tpu_olap.kernels import sparse_groupby as sg
    out = {}
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
            if r.mesh is not None:
                from tpu_olap.executor import sharding as sh
                for cap in (1024,):
                    out[f"{tag}:mesh-sort:{cap}"] = sha(
                        sh.mesh_sparse_kernel(phys, r.mesh, cap).lower(
                            env, valid, seg_arg, consts_dev))
                    tables = jax.eval_shape(
                        sh.mesh_sparse_kernel(phys, r.mesh, cap), env, valid,
                        seg_arg, consts_dev)
                    tables = {k: v for k, v in tables.items()
                              if k != "_count"}
                    out[f"{tag}:mesh-merge:{cap}"] = sha(
                        sh.mesh_merge_kernel(phys, r.mesh, 256).lower(tables))
                continue
            top = r._device_threshold(plan.query, phys) \
                if type(plan.query).__name__.startswith("TopN") else None
            kept = 1024 if r._device_having(phys) else None
            win = r._segment_window(phys, len(seg_mask))

            stored = {c: a.dtype for c, a in env["cols"].items()}
            rows = (win[1] * valid.shape[1]) if win else n

            def lower(cap, narrow):
                read = getattr(sg, "boundary_read", None) if cap else None
                kern = phys.make_sparse_kernel(
                    cap, top, kept if cap else None, narrow,
                    *([read(phys.agg_plans, stored, cap, rows, top,
                            frozenset(env["nulls"]),
                            phys.having[1] if kept else None)]
                      if read else []))
                if win is not None:
                    return jax.jit(r._window_kernel(kern, win[1])).lower(
                        env, valid, seg_arg, consts_dev, win[0])
                return jax.jit(kern).lower(env, valid, seg_arg, consts_dev)

            out[f"{tag}:count"] = sha(lower(None, False))
            for cap in (1024, 4096):
                for narrow in (False, True):
                    out[f"{tag}:cap{cap}:{'narrow' if narrow else 'wide'}"
                        f":n{n}:win{win}:top{bool(top)}:kept{kept}"] = \
                        sha(lower(cap, narrow))
        eng.close()
    out.update(uncut_min_max(sg))
    json.dump(out, sys.stdout, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
