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
the K past it are there to see where the two forms cross.

    python tools/sweep_group_reduce.py                  # on the chip
    python tools/sweep_group_reduce.py --compile-only   # here, for a
        described v5e: compile seconds and temporary bytes, nothing runs
    python tools/sweep_group_reduce.py --allow-cpu --rows 200000   # rehearsal

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

from tpu_olap.kernels import groupby  # noqa: E402

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


FORMS = {"scatter": _scatter, "compare": _compare, "bcast": _bcast}


def _inputs(n, dtype, k, seed=7):
    rng = np.random.default_rng(seed)
    key = rng.integers(0, k, n, dtype=np.int32)
    if dtype == "int64":   # a sum whose rows pass int32
        return rng.integers(0, 1 << 40, n, dtype=np.int64), key
    return (rng.random(n) < 0.5).astype(np.int32), key   # a filtered count


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--ks", type=int, nargs="*", default=list(KS))
    ap.add_argument("--rows", type=int, nargs="*", default=list(ROWS))
    ap.add_argument("--forms", nargs="*", default=list(FORMS),
                    choices=list(FORMS))
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

    out = []
    for n in args.rows:
        n = -(-n // BLOCK) * BLOCK
        for dtype in ("int64", "int32"):
            for k in args.ks:
                if not args.compile_only:
                    v_np, key_np = _inputs(n, dtype, k)
                    v, key = jnp.asarray(v_np), jnp.asarray(key_np)
                    want = np.zeros(k, v_np.dtype)
                    np.add.at(want, key_np, v_np)
                for name, block in [(f, b) for f in args.forms
                                    for b in (args.block_bytes
                                              if f == "compare" else [0])]:
                    form = FORMS[name]
                    groupby._CMP_BLOCK_BYTES = block or \
                        groupby._CMP_BLOCK_BYTES
                    spec = [jax.ShapeDtypeStruct((n,), np.dtype(d),
                                                 sharding=sharding)
                            for d in (dtype, "int32")]
                    t0 = time.perf_counter()
                    compiled = jax.jit(functools.partial(form, k=k)) \
                        .lower(*spec).compile()
                    rec = dict(rows=n, dtype=dtype, k=k, form=name,
                               **({"block_bytes": block} if block else {}),
                               compile_s=round(time.perf_counter() - t0, 2))
                    ma = compiled.memory_analysis()
                    if ma is not None:
                        rec["temp_bytes"] = int(ma.temp_size_in_bytes)
                    if not args.compile_only:
                        got = compiled(v, key)
                        got.block_until_ready()
                        ms = []
                        for _ in range(args.reps):
                            t0 = time.perf_counter()
                            compiled(v, key).block_until_ready()
                            ms.append((time.perf_counter() - t0) * 1e3)
                        rec["ms"] = round(statistics.median(ms), 3)
                        rec["equal"] = bool(
                            np.array_equal(np.asarray(got), want))
                    out.append(rec)
                    print(json.dumps(rec), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
