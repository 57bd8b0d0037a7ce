"""The sparse dispatch's attempt loop alone (executor.sparse_dispatch): a
fake `build` and `probe` in a real runner's locks, jit cache, ledger and
hints, no device program. The three arms differ in what they hand the
loop (their jit key's tag, their budget's noun, whether the groups are
counted first, the chips they note): each case runs under each arm's
settings."""

import types

import numpy as np
import pytest

from tpu_olap import Engine
from tpu_olap.executor import EngineConfig, sparse_dispatch as sd
from tpu_olap.kernels.groupby import AggPlan, UnsupportedAggregation
from tpu_olap.kernels.sparse_groupby import SparseProgram

I64 = np.dtype(np.int64)
LIMIT = 1 << 14
# the arm: (what it calls the groups its probe reads, the chips it notes)
ARMS = {"one-chip": ("present groups", ()),
        "gspmd": ("present groups", ()),
        "chip-each": ("per-chip present groups", range(4))}


@pytest.fixture()
def runner():
    eng = Engine(EngineConfig(fallback_on_device_failure=False))
    # the loop's programs take no device arguments here
    eng.runner._args_for = lambda plan, seg_mask, mesh: ({}, None)
    yield eng.runner
    eng.close()


def _plan(having=False):
    """What `choose_program` reads of a plan: an int64 sum of `x` (stored
    as int8 below: a sum that may ride narrow) and the row count, over a
    group space past the budget."""
    return types.SimpleNamespace(
        fingerprint=lambda: ("t", "template"), query=None,
        sizes=(1, 1 << 40), total_groups=1 << 40, key_words=((0, 1),),
        agg_plans=[AggPlan("s", "sum", ("x",), I64),
                   AggPlan("n", "count", (), I64)],
        having=(None, frozenset({"s"})) if having else None)


def _run(runner, arm, outs, cap=64, having=False, count_first=False):
    """-> (attempt: runs the loop over `outs`, the trees the fake
    programs give in turn, an exception raised in its place; the programs
    enqueued; the dispatch's state)"""
    noun, chips = ARMS[arm]
    plan, programs, outs = _plan(having), [], list(outs)
    d = sd._Dispatch(runner, plan, {}, True, {}, None, None,
                     {"x": np.dtype(np.int8)}, frozenset(), None,
                     sd.sparse_key(plan, 1))

    def build(program):
        def fn(env, valid, seg_arg, consts):
            programs.append(program)
            out = outs.pop(0)
            if isinstance(out, Exception):
                raise out
            return {k: np.int32(v) for k, v in out.items()}
        return fn

    def attempt():
        return sd.attempt_loop(
            d, 1 << 20, (arm,), build, lambda out: int(out["_count"]), cap,
            LIMIT, noun=noun, count_first=count_first, chips=chips)
    return attempt, programs, d


def _pins(runner):
    return dict(runner._hbm_ledger._inflight)


@pytest.mark.parametrize("arm", ARMS)
def test_a_count_over_the_cap_grows_it_and_runs_again(runner, arm):
    attempt, programs, d = _run(runner, arm, [
        {"_count": 100, "_narrow_ok": 1}, {"_count": 100, "_narrow_ok": 1}])
    chips = dict(runner._chip_dispatches)
    fit = attempt()
    assert [p.cap for p in programs] == [64, sd.grown_cap(100, LIMIT)] \
        == [64, 256]
    assert (fit.attempts, fit.count, fit.program) == (2, 100, programs[-1])
    # each cap is a program of its own: two counted compiles
    assert fit.hit is False and d.metrics["recompiles"] == 2
    # the superseded attempt's pin is gone; the tree that fit is pinned
    assert list(_pins(runner)) == [fit.pin]
    noted = {c: n - chips.get(c, 0)
             for c, n in runner._chip_dispatches.items()}
    assert noted == ({c: 2 for c in ARMS[arm][1]} if ARMS[arm][1] else
                     {c: 0 for c in noted})


@pytest.mark.parametrize("arm", ARMS)
@pytest.mark.parametrize("when", ["first-attempt", "after-a-growth",
                                  "counted-first"])
def test_a_count_over_the_budget_raises_the_arms_text(runner, arm, when):
    over = {"_count": LIMIT + 5, "_narrow_ok": 1}
    outs = {"first-attempt": [over],
            "after-a-growth": [{"_count": 100, "_narrow_ok": 1}, over],
            "counted-first": [over]}[when]
    attempt, programs, _ = _run(runner, arm, outs,
                                count_first=when == "counted-first")
    with pytest.raises(UnsupportedAggregation) as e:
        attempt()
    assert str(e.value) == \
        f"{LIMIT + 5} {ARMS[arm][0]} exceed sparse budget {LIMIT}"
    assert not _pins(runner)
    assert (programs[0].cap is None) == (when == "counted-first")


@pytest.mark.parametrize("arm", ARMS)
def test_narrow_not_ok_runs_the_wide_program_once_and_leaves_the_hint(
        runner, arm):
    attempt, programs, d = _run(runner, arm, [
        {"_count": 40, "_narrow_ok": 0}, {"_count": 40}, {"_count": 40}])
    key = d.base_key
    before = runner._m_narrow_fallbacks.value()
    fit = attempt()
    assert [(p.cap, p.narrow) for p in programs] == [(64, True), (64, False)]
    assert fit.attempts == 2 and d.metrics["narrow_fallback"] is True
    assert runner._cap_hints[key + ("wide",)] is True
    assert runner._m_narrow_fallbacks.value() == before + 1
    assert list(_pins(runner)) == [fit.pin]
    # the plan is remembered as wide: its next run starts there
    assert attempt().attempts == 1 and programs[2] == programs[1]


def test_a_narrow_program_past_its_cap_grows_before_it_is_judged(runner):
    """`_narrow_ok` speaks of the runs among `_rows`: past the cap the
    attempt is run again at the grown cap, still narrow."""
    attempt, programs, d = _run(runner, "one-chip", [
        {"_count": 100, "_narrow_ok": 0}, {"_count": 100, "_narrow_ok": 1}])
    assert attempt().attempts == 2
    assert [(p.cap, p.narrow) for p in programs] == [(64, True), (256, True)]
    assert d.base_key + ("wide",) not in runner._cap_hints


@pytest.mark.parametrize("arm", ARMS)
def test_kept_over_the_bucket_grows_the_bucket(runner, arm):
    cap = 1 << 13
    attempt, programs, d = _run(runner, arm, [
        {"_count": 4000, "_narrow_ok": 1, "_kept": 3000},
        {"_count": 4000, "_narrow_ok": 1, "_kept": 3000},
        {"_count": 4000, "_narrow_ok": 1, "_kept": 7}],
        cap=cap, having=True)
    key = d.base_key
    fit = attempt()
    assert [(p.cap, p.kept) for p in programs] == [
        (cap, sd.HAVING_KEPT_MIN), (cap, 4096)]
    assert fit.attempts == 2 and runner._cap_hints[key + ("kept",)] == 3000
    assert list(_pins(runner)) == [fit.pin]
    # a literal that lets fewer through keeps the bucket the template has
    assert attempt().attempts == 1 and programs[2].kept == 4096
    assert runner._cap_hints[key + ("kept",)] == 3000


@pytest.mark.parametrize("arm", ARMS)
def test_a_warm_hint_makes_one_attempt(runner, arm):
    plan = _plan()
    cap = sd.first_cap(runner.config, plan, 100)
    assert cap == sd.grown_cap(100, runner.config.sparse_group_budget) == 256
    attempt, programs, d = _run(runner, arm, [
        {"_count": 100, "_narrow_ok": 1}] * 2, cap=cap)
    fit = attempt()
    assert (fit.attempts, fit.hit) == (1, False) and len(programs) == 1
    assert fit.program == SparseProgram(256, None, None, True, "gather")
    runner._hbm_ledger.unpin_inflight(fit.pin)
    # and the next run finds that program in the jit cache
    again = attempt()
    assert (again.attempts, again.hit) == (1, True)
    assert d.metrics["recompiles"] == 1


def test_counting_first_sizes_the_first_table_from_the_count(runner):
    attempt, programs, _ = _run(runner, "one-chip", [
        {"_count": 300}, {"_count": 300, "_narrow_ok": 1}],
        count_first=True)
    fit = attempt()
    assert programs[0] == SparseProgram(None)   # no table, so no pin
    assert programs[1].cap == sd.grown_cap(300, LIMIT) == 1024
    assert fit.attempts == 2 and list(_pins(runner)) == [fit.pin]


@pytest.mark.parametrize("arm", ARMS)
@pytest.mark.parametrize("fails", ["enqueue", "probe"])
def test_a_pin_is_released_when_an_attempt_fails(runner, arm, fails):
    boom = RuntimeError("the device said no")
    second = boom if fails == "enqueue" else {"_narrow_ok": 1}  # no _count
    attempt, programs, _ = _run(runner, arm, [
        {"_count": 100, "_narrow_ok": 1}, second])
    with pytest.raises(RuntimeError if fails == "enqueue" else KeyError):
        attempt()
    assert len(programs) == 2 and not _pins(runner)
