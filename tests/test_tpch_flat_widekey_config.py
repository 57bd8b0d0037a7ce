"""The deployment `tpch-flat-sf10-widekey-chip` (perfbench/configs) at
60,000 rows on the CPU: the benchmark's own generator through
`Engine.register_table` and `Engine.sql`, TPC-H Q10 and Q18 with the GROUP
BY lists the specification publishes against the benchmark's plain
reference by the comparison that decides `correct` (equality), `q18p` in
the two key words its space takes and `q10p` in the one its space fits at
this size, nothing served by the pandas fallback; what `register` stops,
and what a mesh and the control `x64-off` do."""

import json
import os

import pytest

from perfbench.datasets import tpch_flat_widekey
from perfbench.lib import verify
from tpu_olap import Engine
from tpu_olap.executor import EngineConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 60_000
SEEDS = (17, 2_147_483_659)        # the second beyond 32 signed bits


def _engine_fields():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "tpch-flat-sf10-widekey-chip.json")) as f:
        return json.load(f)["engine_config"]


@pytest.fixture(scope="module", params=SEEDS)
def served(request, tmp_path_factory):
    seed = request.param
    d = tmp_path_factory.mktemp(f"widekey_{seed}")
    data = tpch_flat_widekey.generate(ROWS, seed, str(d), workers=1,
                                      orders_per_chunk=7_000)
    eng = Engine(EngineConfig(**_engine_fields()))
    tpch_flat_widekey.register(eng, data["paths"], ROWS, seed)
    yield eng, data
    eng.close()


def _sql(eng, sql):
    df = eng.sql(sql)
    rec = eng.runner.history[-1]
    assert rec.get("query_type") != "fallback" \
        and "fallback_reason" not in rec and not rec.get("failed"), rec
    return ({"columns": list(df.columns),
             "rows": json.loads(df.to_json(orient="records"))}, rec)


# template -> (key words at this size, at SF10; the key's bits here)
KEY_WORDS = {"q10p": (1, 2), "q18p": (2, 2)}


@pytest.mark.parametrize("name", sorted(KEY_WORDS))
def test_template_equals_the_reference(served, name):
    eng, data = served
    sql = tpch_flat_widekey.templates()[name]
    said = eng.explain(sql)
    assert said["rewritten"] and said["key_words"] == KEY_WORDS[name][0]
    got, rec = _sql(eng, sql)
    expected = tpch_flat_widekey.answers(data["reference"])[name]
    assert verify.answer_mismatches(got, expected) == []
    assert rec["reduce_path"] == "sparse"
    assert rec["reduce_form"] == "boundary"
    assert rec["key_words"] == KEY_WORDS[name][0]
    assert rec["key_bits"] == said["key_bits"]
    if name == "q18p":
        assert rec["having_where"] == "device" and rec["cap_tables"] == 1
        assert rec["sum_word_bits"] == 32
        assert rec["present_groups"] == data["reference"]["n_orders"]
        assert len(got["rows"]) == data["reference"]["groups"]["q18p"]
    else:
        assert rec["sum_word_bits"] == 64
        assert rec["present_groups"] == data["reference"]["groups"]["q10p"]
        assert len(got["rows"]) == 20
        assert any(r["c_acctbal"] < 0 for r in
                   tpch_flat_widekey.reference.ranked(data["reference"],
                                                      "q10p", None))


def test_at_the_published_scale_both_keys_take_two_words():
    """The domains at SF10 (the configuration's `assumed.key_spaces`), in
    each template's GROUP BY order: what lowering packs there."""
    from tpu_olap.kernels.sparse_groupby import key_bits, pack_key_words
    q10p = (1, 1_500_000, 1_000_001, 1_100_000, 26)
    q18p = (1, 1_000_001, 1_500_000, 59_986_000, 2_407, 55_000_000)
    assert [len(pack_key_words(s[1:])) for s in (q10p, q18p)] \
        == [w for _here, w in (KEY_WORDS["q10p"], KEY_WORDS["q18p"])]
    assert (key_bits(q10p), key_bits(q18p)) == (67, 105)


def test_register_stops_a_program_that_does_not_say_key_words(served):
    """The parent's `explain` knows neither key: the run ends before the
    table is ingested. One that says the word with no number (no device
    plan: the control) goes on."""
    eng, data = served

    class Older:
        def __init__(self, drop):
            self.drop = drop
            self.registered = []

        def explain(self, sql):
            said = eng.explain(sql)
            if self.drop == "word":
                said.pop("key_words", None)
            elif self.drop == "number":
                said["key_words"] = None
            return said

        def register_table(self, name, *a, **k):
            self.registered.append(name)
            return eng.register_table(name, *a, **k) \
                if name.endswith("_probe") else None

        def drop_table(self, name):
            return eng.drop_table(name)

    older = Older("word")
    with pytest.raises(SystemExit, match="q10p: it does not say how many"):
        tpch_flat_widekey.register(older, data["paths"], ROWS, 0)
    assert older.registered == [tpch_flat_widekey.TABLE + "_probe"]
    control = Older("number")
    tpch_flat_widekey.register(control, data["paths"], ROWS, 0)
    assert control.registered[-1] == tpch_flat_widekey.TABLE


def test_the_control_has_no_device_plan_and_a_mesh_keeps_q10_in_one_word(
        served):
    _eng, data = served
    off = Engine(EngineConfig(**dict(_engine_fields(), enable_x64=False)))
    tpch_flat_widekey.register(off, data["paths"], ROWS, 0)
    for sql in tpch_flat_widekey.templates().values():
        assert off.explain(sql)["key_words"] is None
    off.close()
    mesh = Engine(EngineConfig(**dict(_engine_fields(), num_shards=4)))
    tpch_flat_widekey.register(mesh, data["paths"], ROWS, 0)
    t = tpch_flat_widekey.templates()
    # the mesh TPC-H cell's q10 (three group columns) stays one word, and
    # so does q10p where its space fits one; q18p's space does not
    assert mesh.explain(t["q10p"])["key_words"] == 1
    assert mesh.explain(t["q18p"])["key_words"] is None
    mesh.sql(t["q18p"])    # the interpreter answers, and says why
    assert "needs one chip: the mesh's merge sorts one int64 key" \
        in mesh.runner.history[-1]["fallback_reason"]
    mesh.close()
