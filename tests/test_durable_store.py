"""Durable segment store (ISSUE 14; docs/DURABILITY.md): checkpointed
sealed segments, WAL truncation, and verified crash recovery.

Covers the tentpole contracts:
- a checkpoint spills the sealed scope as CRC-framed columnar chunks
  plus an atomically-swapped manifest; the layout is canonical, so an
  unchanged sealed set re-checkpoints as a byte-identical noop and
  incremental compaction's untouched segments reuse their chunk files;
- the WAL truncates lag-one (only through the OLDEST retained
  manifest's watermark), so recovery after a checkpoint replays only
  the tail — O(tail), not O(total appends) — and a single corrupt
  newest checkpoint still finds the covering WAL frames on disk;
- the recovery ladder steps over corrupt/missing chunks and torn
  manifests (newest verifiable manifest wins, then the previous, then
  base + WAL) — corruption is detected and surfaced, never served;
- a REAL SIGKILL at each new fault site (spill-write, manifest-swap,
  wal-truncate, store-load) recovers to sha256 parity with a
  never-crashed oracle: zero wrong answers, zero acknowledged-row
  loss;
- recovery edge cases: manifest pointing at a deleted chunk, a
  checkpoint racing concurrent appends, a double crash during recovery
  itself, and close -> reopen -> checkpoint idempotency.

Satellites asserted here too: incremental compaction rewrites only the
delta-touched calendar partitions, the vectorized encode_rows keeps
the original per-row semantics (code order, nulls, atomic rejection),
and backpressure Retry-After derives from the measured compactor
drain rate.
"""

import hashlib
import os
import signal
import threading
import time

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.executor import EngineConfig
from tpu_olap.resilience import FaultInjector
from tpu_olap.resilience.errors import IngestBackpressure, UserError
from tpu_olap.segments.store import (SegmentStore, encode_segment,
                                     StoreCorrupt)
from tpu_olap.segments.wal import replay_wal, wal_path

BLOCK = 512


def _df(n=2000, seed=3):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "ts": pd.to_datetime("2022-03-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 45, n), unit="s"),
        "g": rng.choice([f"g{i}" for i in range(8)], n),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    })


def _cfg(tmp, **kw):
    kw.setdefault("ingest_wal_dir", os.path.join(str(tmp), "wal"))
    kw.setdefault("ingest_store_dir", os.path.join(str(tmp), "store"))
    kw.setdefault("ingest_auto_compact", False)
    kw.setdefault("cube_auto_refresh", False)
    return EngineConfig(**kw)


def _mk(tmp, data=None, **kw):
    eng = Engine(_cfg(tmp, **kw))
    eng.register_table("t", _df() if data is None else data,
                       time_column="ts", block_rows=BLOCK,
                       time_partition="month")
    return eng


def _batch(i, rows=3):
    return [{"ts": f"2022-04-{(i % 27) + 1:02d}T00:00:{j:02d}",
             "g": f"g{(i + j) % 8}", "v": i * 100 + j}
            for j in range(rows)]


def _reference(extra_rows):
    data = _df()
    if extra_rows:
        ext = pd.DataFrame(extra_rows)
        ext["ts"] = pd.to_datetime(ext["ts"], format="mixed")
        data = pd.concat([data, ext], ignore_index=True)
    ref = Engine()
    ref.register_table("t", data, time_column="ts", block_rows=BLOCK,
                       time_partition="month")
    return ref


PARITY_QUERIES = [
    "SELECT g, count(*) AS n, sum(v) AS s FROM t GROUP BY g ORDER BY g",
    "SELECT month(ts) AS mo, sum(v) AS s, min(v) AS lo, max(v) AS hi "
    "FROM t GROUP BY month(ts) ORDER BY mo",
    "SELECT count(*) AS n, sum(v) AS s FROM t WHERE v < 500",
]


def _digest(frame: pd.DataFrame) -> str:
    return hashlib.sha256(
        frame.to_csv(index=False).encode()).hexdigest()


def _assert_parity(eng, ref, label=""):
    for q in PARITY_QUERIES:
        a, b = eng.sql(q), ref.sql(q)
        assert _digest(a) == _digest(b), \
            f"{label}: {q}\n{a}\nvs\n{b}"


def _store_files(tmp):
    d = os.path.join(str(tmp), "store", "t")
    return sorted(os.listdir(d)), d


def _manifest_refs(tmp, which=-1):
    """Chunk files referenced by one retained manifest (newest = -1)."""
    import json
    names, d = _store_files(tmp)
    manifests = [n for n in names if n.startswith("manifest-")]
    with open(os.path.join(d, manifests[which]), "rb") as f:
        payload = json.load(f)["payload"]
    refs = {e["file"] for e in payload["segments"]}
    refs.add(payload["dictionary"]["file"])
    return refs, payload


# -------------------------------------------------- checkpoint basics

def test_checkpoint_spill_noop_and_canonical_respill(tmp_path):
    eng = _mk(tmp_path)
    for i in range(4):
        eng.append("t", _batch(i))
    res = eng.checkpoint_now("t")
    assert res["status"] == "checkpointed" and res["checkpoint_id"] == 1
    assert res["files_written"] > 0
    # canonical layout: re-encoding an unchanged segment is
    # byte-identical, so an unchanged sealed set re-checkpoints as a
    # pure noop (no files written, no new manifest)
    seg = eng.catalog.get("t").segments.segments[0]
    assert encode_segment(seg) == encode_segment(seg)
    res2 = eng.checkpoint_now("t")
    assert res2["status"] == "noop" and res2["files_written"] == 0
    # the store directory holds content-addressed chunks + 1 manifest
    names, _ = _store_files(tmp_path)
    assert any(n.startswith("seg-") for n in names)
    assert any(n.startswith("dict-") for n in names)
    assert sum(n.startswith("manifest-") for n in names) == 1
    eng.close()


def test_checkpoint_truncates_wal_lag_one(tmp_path):
    eng = _mk(tmp_path)
    wal = wal_path(eng.config.ingest_wal_dir, "t")
    for i in range(4):
        eng.append("t", _batch(i))
    r1 = eng.checkpoint_now("t")
    # first checkpoint: only one manifest retained -> nothing may be
    # truncated yet (the lag-one guarantee needs a previous rung)
    assert r1["status"] == "checkpointed"
    assert r1["wal_frames_truncated"] == 0
    assert len(replay_wal(wal)) == 4
    for i in range(4, 6):
        eng.append("t", _batch(i))
    r2 = eng.checkpoint_now("t")
    assert r2["status"] == "checkpointed"
    # second checkpoint truncates exactly the frames the FIRST (now
    # oldest retained) manifest covers
    assert r2["wal_frames_truncated"] == 4
    kept = replay_wal(wal)
    assert [s for s, _ in kept] == [5, 6]
    # acknowledged seq counters never rewind
    st = eng.ingest._state("t")
    assert st.acked_seq == 6
    _assert_parity(eng, _reference(
        [r for i in range(6) for r in _batch(i)]), "post-truncate")
    eng.close()


def test_recovery_replays_only_tail(tmp_path):
    eng = _mk(tmp_path)
    for i in range(6):
        eng.append("t", _batch(i))
    eng.checkpoint_now("t")
    tail = [_batch(i) for i in range(6, 8)]
    for b in tail:
        eng.append("t", b)
    eng.close()
    rec = _mk(tmp_path)
    ev = [e for e in rec.runner.events.snapshot()
          if e["event"] == "wal_replay"]
    loads = [e for e in rec.runner.events.snapshot()
             if e["event"] == "store_load"]
    assert loads and loads[0]["wal_seq"] == 6
    # O(tail): only the 2 post-checkpoint frames replayed, not all 8
    assert ev and ev[0]["records"] == 2
    _assert_parity(rec, _reference(
        [r for i in range(8) for r in _batch(i)]), "tail-only")
    # recovered acked seq continues the original sequence
    assert rec.ingest._state("t").acked_seq == 8
    rec.close()


def test_checkpoint_on_compact_auto_hook(tmp_path):
    eng = _mk(tmp_path)  # ingest_store_checkpoint_on_compact defaults on
    for i in range(3):
        eng.append("t", _batch(i))
    res = eng.compact_now("t")
    assert res["status"] == "compacted"
    assert res["checkpoint"]["status"] == "checkpointed"
    st = eng.ingest._state("t")
    assert st.checkpoints == 1 and st.sealed_through_seq == 3
    eng.close()


def test_no_store_dir_disables_checkpointing(tmp_path):
    eng = _mk(tmp_path, ingest_store_dir=None)
    eng.append("t", _batch(0))
    res = eng.checkpoint_now("t")
    assert res["status"] == "no-store"
    out = eng.sql("CHECKPOINT DRUID TABLE t")
    assert out["status"][0] == "no-store"
    eng.close()


# ---------------------------------------------------- recovery ladder

def _build_two_checkpoints(tmp_path):
    """acked batches 0..7: 0-3 in ck1, 4-5 in ck2, 6-7 WAL tail."""
    eng = _mk(tmp_path)
    for i in range(4):
        eng.append("t", _batch(i))
    eng.checkpoint_now("t")
    for i in range(4, 6):
        eng.append("t", _batch(i))
    eng.checkpoint_now("t")
    for i in range(6, 8):
        eng.append("t", _batch(i))
    eng.close()
    return [r for i in range(8) for r in _batch(i)]


def test_corrupt_newest_manifest_falls_back_one_rung(tmp_path):
    acked = _build_two_checkpoints(tmp_path)
    names, d = _store_files(tmp_path)
    newest = [n for n in names if n.startswith("manifest-")][-1]
    with open(os.path.join(d, newest), "r+b") as f:
        f.seek(40)
        b = f.read(1)
        f.seek(40)
        f.write(bytes([b[0] ^ 0xFF]))
    rec = _mk(tmp_path)
    loads = [e for e in rec.runner.events.snapshot()
             if e["event"] == "store_load"]
    falls = [e for e in rec.runner.events.snapshot()
             if e["event"] == "store_fallback"]
    assert falls and falls[0]["manifest"] == newest
    # the previous manifest won; the lag-one WAL tail covers the rest
    assert loads and loads[0]["wal_seq"] == 4
    _assert_parity(rec, _reference(acked), "ladder rung 2")
    rec.close()


def test_manifest_pointing_at_deleted_chunk(tmp_path):
    acked = _build_two_checkpoints(tmp_path)
    refs2, _ = _manifest_refs(tmp_path, -1)
    refs1, _ = _manifest_refs(tmp_path, 0)
    only_newest = sorted(refs2 - refs1)
    assert only_newest, "checkpoint 2 wrote no fresh chunk"
    _, d = _store_files(tmp_path)
    os.unlink(os.path.join(d, only_newest[0]))
    rec = _mk(tmp_path)
    falls = [e for e in rec.runner.events.snapshot()
             if e["event"] == "store_fallback"]
    assert falls and "missing chunk" in falls[0]["reason"]
    _assert_parity(rec, _reference(acked), "deleted chunk")
    rec.close()


def test_bitflip_corruption_campaign(tmp_path):
    """Flip one byte in every recoverable spill file, one at a time:
    both manifests, every chunk not shared by all retained rungs.
    Each flip must be DETECTED (fallback event, never a crash) and
    recovery must reach sha256 parity with the never-crashed oracle.
    A chunk shared by every retained manifest is the single durable
    copy of pre-checkpoint rows (the WAL below the oldest watermark is
    truncated) — flipping it exercises the ladder floor instead:
    detected, surfaced, and the registration REFUSED (a coverage gap
    between the surviving WAL and what any rung covers must never
    silently serve a table missing acknowledged rows)."""
    acked = _build_two_checkpoints(tmp_path)
    ref = _reference(acked)
    refs2, _ = _manifest_refs(tmp_path, -1)
    refs1, _ = _manifest_refs(tmp_path, 0)
    names, d = _store_files(tmp_path)
    manifests = [n for n in names if n.startswith("manifest-")]
    recoverable = manifests + sorted(refs1 ^ refs2)
    flipped = 0
    for fname in recoverable:
        path = os.path.join(d, fname)
        with open(path, "rb") as f:
            orig = f.read()
        pos = len(orig) // 2
        with open(path, "wb") as f:
            f.write(orig[:pos] + bytes([orig[pos] ^ 0x55])
                    + orig[pos + 1:])
        rec = _mk(tmp_path)
        _assert_parity(rec, ref, f"bit-flip {fname}")
        rec.close()
        with open(path, "wb") as f:
            f.write(orig)
        flipped += 1
    assert flipped >= 3, "campaign too small to prove anything"
    # ladder floor: a chunk shared by ALL retained manifests is a
    # single copy — both rungs fail, and because the WAL below the
    # oldest watermark is truncated there is a coverage gap the
    # recovery must REFUSE to paper over
    shared = sorted(refs1 & refs2)
    assert shared, "no shared chunk — dedup across checkpoints broke"
    path = os.path.join(d, shared[0])
    with open(path, "rb") as f:
        orig = f.read()
    with open(path, "wb") as f:
        f.write(orig[:64] + bytes([orig[64] ^ 0x55]) + orig[65:])
    rec = Engine(_cfg(tmp_path))
    with pytest.raises(RuntimeError, match="recovery .* refused"):
        rec.register_table("t", _df(), time_column="ts",
                           block_rows=BLOCK, time_partition="month")
    falls = [e for e in rec.runner.events.snapshot()
             if e["event"] == "store_fallback"]
    assert len(falls) >= 2  # both rungs detected the corruption
    rec.close()
    # restoring the chunk makes the same registration recover fully
    with open(path, "wb") as f:
        f.write(orig)
    rec = _mk(tmp_path)
    _assert_parity(rec, ref, "restored shared chunk")
    rec.close()


def test_all_manifests_corrupt_before_truncation_full_replay(tmp_path):
    """With a single checkpoint nothing was truncated yet, so losing
    EVERY manifest still recovers fully from base + the whole WAL."""
    eng = _mk(tmp_path)
    for i in range(4):
        eng.append("t", _batch(i))
    eng.checkpoint_now("t")
    eng.close()
    names, d = _store_files(tmp_path)
    for n in names:
        if n.startswith("manifest-"):
            with open(os.path.join(d, n), "ab") as f:
                f.truncate(10)  # torn manifest
    rec = _mk(tmp_path)
    ev = [e for e in rec.runner.events.snapshot()
          if e["event"] == "wal_replay"]
    assert ev and ev[0]["records"] == 4
    _assert_parity(rec, _reference(
        [r for i in range(4) for r in _batch(i)]), "base+full WAL")
    rec.close()


def test_store_unit_load_ladder_reports_fallbacks(tmp_path):
    """SegmentStore.load in isolation: corrupt newest -> previous wins
    with the rung recorded; all corrupt -> LoadedCheckpoint with
    segments None (base-only), never an exception."""
    acked = _build_two_checkpoints(tmp_path)
    del acked
    store = SegmentStore(os.path.join(str(tmp_path), "store"))
    loaded = store.load("t")
    assert loaded.segments is not None and not loaded.fallbacks
    names, d = _store_files(tmp_path)
    for n in names:
        if n.startswith("seg-") or n.startswith("dict-"):
            with open(os.path.join(d, n), "r+b") as f:
                f.seek(8)
                f.write(b"\x00\x00\x00\x00")
    loaded = store.load("t")
    assert loaded.segments is None and len(loaded.fallbacks) == 2
    assert store.load("missing") is None
    with pytest.raises(StoreCorrupt):
        store._read_manifest(os.path.join(d, "manifest-absent.json"))


# ------------------------------------------------ SIGKILL chaos suite

KILL_SITES = ("spill-write", "manifest-swap", "wal-truncate",
              "store-load")


class _KillAt:
    """Fault injector that dies for real — no unwind, no atexit."""

    def __init__(self, stage):
        self.stages = {stage}

    def __call__(self, stage, attempt):
        os.kill(os.getpid(), signal.SIGKILL)


@pytest.mark.parametrize("site", KILL_SITES)
def test_sigkill_at_fault_site_recovers_to_parity(site, tmp_path):
    """Fork a child that SIGKILLs itself exactly at the fault site
    mid-checkpoint (or mid-recovery for store-load), then recover in
    the parent and assert sha256 parity with a never-crashed oracle.
    The forked child must never touch the parent's jax runtime:
    append / checkpoint / store-load place nothing on a device, and a
    child that built a DeviceDataset all the same exits 87 at once."""
    def no_device(table):
        os._exit(87)

    pid = os.fork()
    if pid == 0:
        try:
            eng = _mk(tmp_path, ingest_wal_fsync="always")
            eng.runner._dataset = no_device
            for i in range(3):
                eng.append("t", _batch(i))
            eng.checkpoint_now("t")
            for i in range(3, 6):
                eng.append("t", _batch(i))
            if site == "store-load":
                # recovery-side site: crash while LOADING the store —
                # a second in-child engine over the same dirs
                eng2 = Engine(_cfg(tmp_path))
                eng2.runner._dataset = no_device
                eng2.config.fault_injector = _KillAt(site)
                eng2.register_table("t", _df(), time_column="ts",
                                    block_rows=BLOCK,
                                    time_partition="month")
            else:
                eng.config.fault_injector = _KillAt(site)
                eng.checkpoint_now("t")
        except BaseException:
            pass
        os._exit(86)  # the fault never fired
    _, status = os.waitpid(pid, 0)
    assert os.WIFSIGNALED(status) \
        and os.WTERMSIG(status) == signal.SIGKILL, \
        f"child exited {status} without hitting {site}"
    rec = _mk(tmp_path)
    # every acknowledged append survived: 3 checkpointed + 3 tail
    assert rec.ingest._state("t").acked_seq == 6
    _assert_parity(rec, _reference(
        [r for i in range(6) for r in _batch(i)]), f"SIGKILL {site}")
    rec.close()


def test_seeded_inprocess_chaos_all_store_sites(tmp_path):
    """Seeded RuntimeError chaos at every store site interleaved with
    appends/checkpoints (the in-process spelling the PR 13 suite
    established); the abandoned-state files must always recover."""
    eng = _mk(tmp_path)
    inj = FaultInjector(seed=23, rate=0.35,
                        stages={"spill-write", "manifest-swap",
                                "wal-truncate", "compact"})
    eng.config.fault_injector = inj
    rng = np.random.default_rng(23)
    acked = []
    for i in range(24):
        rows = _batch(i)
        try:
            eng.append("t", rows)
            acked.extend(rows)
        except RuntimeError:
            pass
        if rng.random() < 0.4:
            try:
                res = eng.checkpoint_now("t")
                assert res["status"] in ("checkpointed", "noop",
                                         "busy", "error", "compacted",
                                         "breaker-open")
            except RuntimeError:
                pass  # injected mid-spill: previous manifest stands
    assert inj.faults > 0, "chaos never fired"
    eng.config.fault_injector = None
    eng.close()
    rec = _mk(tmp_path)
    _assert_parity(rec, _reference(acked), "in-process chaos")
    rec.close()


# -------------------------------------------------- recovery edge cases

def test_double_crash_during_recovery(tmp_path):
    """Crash while recovering (store-load), then crash again while
    replaying the tail (wal-replay): each retry starts the ladder
    clean and the third attempt recovers fully."""
    acked = _build_two_checkpoints(tmp_path)
    rec = Engine(_cfg(tmp_path))
    rec.config.fault_injector = FaultInjector(
        seed=1, rate=1.0, stages={"store-load"})
    with pytest.raises(RuntimeError):
        rec.register_table("t", _df(), time_column="ts",
                           block_rows=BLOCK, time_partition="month")
    rec.config.fault_injector = FaultInjector(
        seed=2, rate=1.0, stages={"wal-replay"})
    with pytest.raises(RuntimeError):
        rec.register_table("t", _df(), time_column="ts",
                           block_rows=BLOCK, time_partition="month")
    rec.config.fault_injector = None
    rec.register_table("t", _df(), time_column="ts",
                       block_rows=BLOCK, time_partition="month")
    _assert_parity(rec, _reference(acked), "double crash")
    rec.close()


def test_checkpoint_racing_concurrent_appends(tmp_path):
    """Appends on a real thread while checkpoints run: the watermark
    only ever covers rows actually in the sealed scope, nothing acked
    is lost, and a cold-start recovery reaches parity."""
    eng = _mk(tmp_path)
    acked = []
    alock = threading.Lock()
    stop = threading.Event()

    def writer():
        i = 100
        while not stop.is_set():
            rows = _batch(i)
            eng.append("t", rows)
            with alock:
                acked.extend(rows)
            i += 1

    th = threading.Thread(target=writer)
    th.start()
    try:
        for _ in range(5):
            res = eng.checkpoint_now("t")
            assert res["status"] in ("checkpointed", "noop", "busy")
            st = eng.ingest._state("t")
            assert st.sealed_through_seq <= st.acked_seq
            time.sleep(0.02)
    finally:
        stop.set()
        th.join()
    with alock:
        n_acked = len(acked)
    got = int(eng.sql("SELECT count(*) AS n FROM t")["n"][0])
    assert got == 2000 + n_acked
    eng.close()
    rec = _mk(tmp_path)
    _assert_parity(rec, _reference(acked), "racing appends")
    rec.close()


def test_noop_checkpoint_still_truncates_wal(tmp_path):
    """Crash in the wal-truncate window (manifest swapped, log not yet
    rewritten): the next checkpoint of the unchanged sealed set is a
    noop, but it must still truncate the covered prefix — otherwise
    the frames persist forever."""
    eng = _mk(tmp_path)
    wal = wal_path(eng.config.ingest_wal_dir, "t")
    for i in range(3):
        eng.append("t", _batch(i))
    eng.checkpoint_now("t")
    for i in range(3, 5):
        eng.append("t", _batch(i))
    eng.config.fault_injector = FaultInjector(
        seed=3, rate=1.0, stages={"wal-truncate"})
    with pytest.raises(RuntimeError):
        eng.checkpoint_now("t")  # manifest advanced, truncation died
    eng.config.fault_injector = None
    assert len(replay_wal(wal)) == 5  # covered prefix still on disk
    res = eng.checkpoint_now("t")
    assert res["status"] == "noop"
    assert res["wal_frames_truncated"] == 3
    assert [s for s, _ in replay_wal(wal)] == [4, 5]
    _assert_parity(eng, _reference(
        [r for i in range(5) for r in _batch(i)]), "noop truncate")
    eng.close()


def test_stale_checkpoint_after_replacement_is_discarded(tmp_path):
    """A checkpoint commit that loses the race with a re-registration
    must not survive it: a manifest of the REPLACED data with the old
    high watermark would make the next recovery silently drop every
    newly acknowledged row. Simulates the race's late half by driving
    _checkpoint_sealed with the displaced state object."""
    eng = _mk(tmp_path)
    eng.append("t", _batch(0))
    old_entry = eng.catalog.get("t")
    old_st = eng.ingest._state("t")
    # the replacement lands mid-checkpoint (before the commit check)
    eng.register_table("t", _df(seed=9), time_column="ts",
                       block_rows=BLOCK, time_partition="month")
    res = eng.ingest._checkpoint_sealed("t", old_entry, old_st)
    assert res["status"] == "stale"
    assert not os.path.isdir(os.path.join(str(tmp_path), "store", "t"))
    # new appends recover normally — nothing resurrected, nothing lost
    eng.append("t", _batch(1))
    eng.close()
    rec = Engine(_cfg(tmp_path))
    rec.register_table("t", _df(seed=9), time_column="ts",
                       block_rows=BLOCK, time_partition="month")
    n = int(rec.sql("SELECT count(*) AS n FROM t")["n"][0])
    assert n == 2000 + 3  # base(seed 9) + the post-replacement batch
    rec.close()


def test_close_reopen_checkpoint_idempotent(tmp_path):
    eng = _mk(tmp_path)
    for i in range(3):
        eng.append("t", _batch(i))
    r1 = eng.checkpoint_now("t")
    assert r1["status"] == "checkpointed"
    eng.close()
    rec = _mk(tmp_path)
    # nothing changed across the restart: the sealed scope re-spills
    # byte-identically and the manifest does not advance
    r2 = rec.checkpoint_now("t")
    assert r2["status"] == "noop" and r2["files_written"] == 0
    rec.close()
    rec2 = _mk(tmp_path)
    _assert_parity(rec2, _reference(
        [r for i in range(3) for r in _batch(i)]), "reopen x2")
    rec2.close()


def test_reregistering_live_table_drops_store(tmp_path):
    eng = _mk(tmp_path)
    eng.append("t", _batch(0))
    eng.checkpoint_now("t")
    _, d = _store_files(tmp_path)
    assert os.path.isdir(d)
    # replacing a LIVE table: its checkpoints covered the old data
    eng.register_table("t", _df(seed=9), time_column="ts",
                       block_rows=BLOCK, time_partition="month")
    assert not os.path.isdir(d)
    n = int(eng.sql("SELECT count(*) AS n FROM t")["n"][0])
    assert n == 2000  # no resurrected appends
    eng.close()


def test_drop_table_deletes_store(tmp_path):
    eng = _mk(tmp_path)
    eng.append("t", _batch(0))
    eng.checkpoint_now("t")
    _, d = _store_files(tmp_path)
    eng.drop_table("t")
    assert not os.path.isdir(d)
    eng.close()


# ------------------------------------------- incremental compaction

def test_incremental_compaction_rewrites_only_touched(tmp_path):
    """Base spans months 3-4/2022; appends land in April only. The
    compactor must reuse March's sealed segments (mode=incremental)
    and the next checkpoint must reuse their spilled chunks."""
    eng = _mk(tmp_path)
    eng.checkpoint_now("t")  # spill the pristine base
    before = eng.catalog.get("t").segments
    march = [s for s in before.segments
             if pd.Timestamp(s.meta.time_min, unit="ms").month == 3]
    assert march, "base has no March partition"
    for i in range(3):
        eng.append("t", _batch(i))  # April timestamps only
    res = eng.compact_now("t")
    assert res["mode"] == "incremental"
    assert res["segments_reused"] >= len(march)
    # the reused segments' chunk files were NOT rewritten
    ck = res["checkpoint"]
    assert ck["status"] == "checkpointed"
    assert ck["chunks_reused"] >= len(march)
    _assert_parity(eng, _reference(
        [r for i in range(3) for r in _batch(i)]), "incremental")
    eng.close()


def test_unsorted_dictionary_forces_full_compaction(tmp_path):
    eng = _mk(tmp_path)
    # an unseen value tail-extends the dictionary -> unsorted ->
    # incremental ineligible (stored codes would need a re-sort)
    eng.append("t", [{"ts": "2022-04-02T00:00:00", "g": "aaa_new",
                      "v": 5}])
    assert not eng.catalog.get("t").segments.dictionaries["g"].is_sorted
    res = eng.compact_now("t")
    assert res["mode"] == "full"
    assert eng.catalog.get("t").segments.dictionaries["g"].is_sorted
    _assert_parity(eng, _reference(
        [{"ts": "2022-04-02T00:00:00", "g": "aaa_new", "v": 5}]),
        "full fallback")
    eng.close()


# -------------------------------------------- vectorized encode_rows

def test_vectorized_encode_rows_semantics():
    """The numpy batch encoder keeps the per-row loop's observable
    contract: unseen values coded in first-appearance order, None
    folds to SQL NULL (NaN-in-LONG still rejects, like int(nan)
    always did), and a bad value rejects the batch whole."""
    eng = Engine(EngineConfig(ingest_auto_compact=False,
                              cube_auto_refresh=False))
    eng.register_table("t", _df(), time_column="ts", block_rows=BLOCK)
    rows = [
        {"ts": "2022-04-01T00:00:00", "g": "zz", "v": 1},
        {"ts": "2022-04-01T00:00:01", "g": "aa", "v": None},
        {"ts": "2022-04-01T00:00:02", "g": "zz", "v": None},
        {"ts": "2022-04-01T00:00:03", "g": None, "v": 4},
        {"ts": "2022-04-01T00:00:04", "g": "mm", "v": 5},
    ]
    eng.append("t", rows)
    d = eng.catalog.get("t").segments.dictionaries["g"]
    # first-appearance tail order — the exact codes the original
    # per-row sequence assigned (WAL replay block-identity)
    assert list(d.values[-3:]) == ["zz", "aa", "mm"]
    got = eng.sql("SELECT count(*) AS n, count(v) AS nv, sum(v) AS s "
                  "FROM t WHERE g IN ('zz', 'aa', 'mm')")
    assert int(got["n"][0]) == 4 and int(got["nv"][0]) == 2
    assert int(got["s"][0]) == 6
    before = eng.catalog.get("t").segments.delta_rows
    with pytest.raises(UserError, match="LONG"):
        eng.append("t", [
            {"ts": "2022-04-01T00:00:00", "g": "x", "v": 1},
            {"ts": "2022-04-01T00:00:01", "g": "x", "v": "junk"}])
    assert eng.catalog.get("t").segments.delta_rows == before


def test_vectorized_encode_rows_throughput_floor():
    """The batch encoder must beat the old ~13k rows/s per-row loop by
    a wide margin; assert a conservative floor so a regression back to
    per-row Python work fails loudly."""
    eng = Engine(EngineConfig(ingest_auto_compact=False,
                              cube_auto_refresh=False,
                              ingest_max_delta_rows=1 << 22))
    eng.register_table("t", _df(), time_column="ts", block_rows=BLOCK)
    n = 50_000
    rng = np.random.default_rng(0)
    base_ms = int(pd.Timestamp("2022-04-01").value // 10 ** 6)
    rows = [{"ts": base_ms + int(x), "g": f"g{int(c)}", "v": int(v)}
            for x, c, v in zip(rng.integers(0, 10 ** 9, n),
                               rng.integers(0, 8, n),
                               rng.integers(0, 1000, n))]
    t0 = time.perf_counter()
    eng.append("t", rows)
    rps = n / (time.perf_counter() - t0)
    assert rps > 40_000, f"encode_rows regressed to {rps:,.0f} rows/s"


# ------------------------------------ drain-rate-derived Retry-After

def test_retry_after_derives_from_measured_drain_rate(tmp_path):
    eng = _mk(tmp_path, ingest_max_delta_rows=64,
              ingest_store_checkpoint_on_compact=False)
    st = eng.ingest._state("t")
    assert st.drain_rps is None
    # before any compaction: the fixed config constant
    for i in range(21):
        eng.append("t", _batch(i))  # 63 rows
    with pytest.raises(IngestBackpressure) as e1:
        eng.append("t", _batch(99))
    assert e1.value.retry_after_s \
        == pytest.approx(eng.config.ingest_retry_after_s)
    eng.compact_now("t")  # observes the drain rate
    assert st.drain_rps and st.drain_rps > 0
    for i in range(21):
        eng.append("t", _batch(i))
    with pytest.raises(IngestBackpressure) as e2:
        eng.append("t", _batch(99))
    need = 63 + 3 - 64
    lo, hi = eng.ingest._RETRY_AFTER_BOUNDS
    expect = min(hi, max(lo, need / st.drain_rps))
    assert e2.value.retry_after_s == pytest.approx(expect)
    snap = eng.ingest.snapshot()["tables"]["t"]
    assert snap["drain_rows_per_s"] == round(st.drain_rps, 1)
    eng.close()


# ------------------------------------------------ surfaces & contract

def test_sys_checkpoints_and_debug_surfaces(tmp_path):
    eng = _mk(tmp_path)
    for i in range(3):
        eng.append("t", _batch(i))
    out = eng.sql("CHECKPOINT DRUID TABLE t")
    assert out["status"][0] == "checkpointed"
    rows = eng.sql("SELECT * FROM sys.checkpoints")
    assert list(rows["table"]) == ["t"]
    r = rows.iloc[0]
    assert int(r["checkpoint_id"]) == 1
    assert int(r["wal_watermark"]) == 3
    assert int(r["acked_seq"]) == 3
    assert int(r["checkpoints"]) == 1
    assert r["last_status"] == "checkpointed"
    snap = eng.ingest.snapshot()
    assert snap["store"]["dir"] == eng.config.ingest_store_dir
    tstore = snap["tables"]["t"]["store"]
    assert tstore["checkpoints"] == 1
    assert tstore["sealed_through_seq"] == 3
    # metrics registered and counting
    text = eng.runner.metrics.render()
    assert "checkpoints_total" in text
    assert "store_bytes" in text
    eng.close()
