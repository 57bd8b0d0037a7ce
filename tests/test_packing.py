"""The packed single-fetch buffer (executor/packing.py): the f32-pair
encoding float64 slabs take on the TPU backend, exercised on the CPU."""

import numpy as np
import pytest

from tpu_olap import Engine
from tpu_olap.bench.parity import assert_frame_parity
from tpu_olap.bench.ssb import generate_tables, register_ssb
from tpu_olap.executor import EngineConfig, packing, runner


def _roundtrip(values, f64_as_pair):
    import jax.numpy as jnp
    EngineConfig().apply_x64()  # as every engine does at start
    x = np.asarray(values, np.float64)
    layout = packing.PackLayout(len(x), len(x),
                                (("v", np.dtype(np.float64)),),
                                f64_as_pair=f64_as_pair)
    words = packing._as_words(jnp.asarray(x), f64_as_pair)
    buf = np.concatenate([np.asarray([len(x)], np.int32),
                          np.arange(len(x), dtype=np.int32),
                          np.asarray(words)])
    count, idx, arrays = packing.unpack(buf, layout)
    assert count == len(x) and list(idx) == list(range(len(x)))
    return x, arrays["v"]


@pytest.mark.parametrize("f64_as_pair", [False, True])
def test_f64_slab_keeps_integers_and_non_finite(f64_as_pair):
    # every integer an f32 pair can hold (|v| < 2^48) comes back exact,
    # and nan / inf are not turned into one another by the split
    x, got = _roundtrip([0.0, -1.0, 16_777_217.0, 2.0**47 + 1, -(2.0**40 + 3),
                         np.nan, np.inf, -np.inf], f64_as_pair)
    np.testing.assert_array_equal(got, x)


def test_f64_pair_carries_48_bits():
    rng = np.random.default_rng(0)
    x, got = _roundtrip(rng.normal(size=4096) * 10.0 ** rng.integers(
        -20, 20, 4096), True)
    np.testing.assert_allclose(got, x, rtol=2.0 ** -46, atol=0)


PAIR_SQL = {
    "minmax": """SELECT d_year, min(lo_revenue) AS lo,
                        max(lo_supplycost) AS hi, sum(lo_revenue) AS rev
                 FROM lineorder GROUP BY d_year ORDER BY d_year""",
    "hll": """SELECT s_region, approx_count_distinct(lo_custkey) AS u
              FROM lineorder JOIN supplier ON lo_suppkey = s_suppkey
              GROUP BY s_region ORDER BY s_region""",
    "doublesum": """SELECT d_year, sum(lo_revenue * 0.5) AS half,
                           avg(lo_quantity) AS q
                    FROM lineorder GROUP BY d_year ORDER BY d_year""",
}


@pytest.fixture(scope="module")
def ssb_engine():
    eng = Engine(EngineConfig(fallback_on_device_failure=False))
    register_ssb(eng, tables=generate_tables(60_000, seed=3))
    return eng


@pytest.mark.parametrize("name", list(PAIR_SQL))
def test_pair_layout_answers_like_the_bitcast_layout(ssb_engine, monkeypatch,
                                                     name):
    """The packed program the TPU backend builds (f64 slabs as f32
    pairs), run here on XLA:CPU, against the bitcast layout."""
    want = ssb_engine.sql(PAIR_SQL[name])
    assert ssb_engine.runner.history[-1]["packed"]
    ssb_engine.clear_cache()

    def as_tpu(plan, config, cap=None, backend=None):
        return packing.make_layout(plan, config, cap, "tpu")

    monkeypatch.setattr(runner, "make_layout", as_tpu)
    got = ssb_engine.sql(PAIR_SQL[name])
    rec = ssb_engine.runner.history[-1]
    assert rec["packed"] and not rec.get("jit_cache_hit")
    assert_frame_parity(got, want, float_rtol=2.0 ** -45, float_atol=0,
                        ordered=True, label=name)
