"""Device-resident column cache: stacked [n_segments, block_rows] arrays.

The analog of Druid historicals' memory-mapped segments (SURVEY.md §2 L1):
columns are uploaded to the device once per table (lazily, per column) and
reused across queries — the Parquet→HBM streaming half of BASELINE.json:5.
Interval pruning is applied as a per-segment mask (columns stay resident;
masked segments cost compute but no transfer — the dense-scan tradeoff).
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np

from tpu_olap.segments.segment import ColumnType, TableSegments, TIME_COLUMN


class HbmLedger:
    """LRU accounting of device-resident column buffers across every
    table the runner serves (SURVEY.md §8.4 #4: "v5e-8 HBM budget forces
    column discipline"). When an upload would exceed the budget, the
    least-recently-used unpinned buffers are evicted first; buffers the
    in-flight query needs are pinned for the duration of its env build.
    A single over-budget column still uploads (the query must run) —
    the budget bounds the cache, not one query's working set.

    In-flight result pinning (pipelined execution, docs/PERF_MODEL.md):
    between stage-1 enqueue and stage-2 transfer, a dispatch's output
    buffers live in HBM outside the column cache. `pin_inflight` counts
    those bytes toward the budget — so a concurrent query's env build
    evicts resident columns to make room rather than silently
    overcommitting HBM — and they are never themselves evictable (the
    transfer is about to read them). Mutations are internally locked:
    stage-2 unpins run lock-free with respect to dispatch_lock."""

    def __init__(self, budget_bytes: int | None, num_chips: int = 1):
        self.budget = budget_bytes
        self._entries: OrderedDict[tuple, tuple[int, object]] = \
            OrderedDict()  # key -> (nbytes, evict_fn)
        self._inflight: dict[tuple, int] = {}  # pinned result buffers
        self._mu = threading.RLock()
        self.bytes_in_use = 0
        self.evictions = 0
        # per-(chip, owner-class) attribution (ISSUE 17): under a mesh
        # every ledgered buffer is sharded EXACTLY 1/num_chips per chip
        # (DeviceDataset pads the segment axis to a multiple of D), so
        # a per-entry even split is the true placement, not an
        # estimate. Shares distribute any byte remainder to the lowest
        # chips deterministically, so per-chip sums always equal
        # bytes_in_use exactly. High-watermarks track ledger-managed
        # bytes at mutation time; external reporters (tier-1 cache
        # pins) are pulled live at breakdown time.
        self.num_chips = max(1, int(num_chips))
        self._chip_bytes = [0] * self.num_chips
        self._chip_hwm = [0] * self.num_chips
        self.high_watermark = 0
        self._by_chip_owner: dict[tuple, int] = {}
        self._external: dict = {}  # owner -> fn(num_chips) -> {chip: b}

    # ------------------------------------------- per-chip attribution

    @staticmethod
    def _owner_for(key) -> str:
        """Owner class of a ledger key: in-flight result pins, cube
        tables (catalog name `__cube_<name>`), or ordinary table
        columns (col/null/derived stacks)."""
        head = str(key[0]) if key else ""
        if head == "__inflight__":
            return "inflight"
        if head.startswith("__cube"):
            return "cube_tables"
        return "table_columns"

    def _shares(self, nbytes: int) -> list:
        q, r = divmod(int(nbytes), self.num_chips)
        return [q + (1 if c < r else 0) for c in range(self.num_chips)]

    def _account(self, key, nbytes: int, sign: int):
        """Incremental per-(chip, owner) bookkeeping; caller holds _mu
        and has already updated bytes_in_use."""
        owner = self._owner_for(key)
        for c, share in enumerate(self._shares(nbytes)):
            self._chip_bytes[c] += sign * share
            k = (c, owner)
            nb = self._by_chip_owner.get(k, 0) + sign * share
            if nb:
                self._by_chip_owner[k] = nb
            else:
                self._by_chip_owner.pop(k, None)
            if sign > 0 and self._chip_bytes[c] > self._chip_hwm[c]:
                self._chip_hwm[c] = self._chip_bytes[c]
        if sign > 0 and self.bytes_in_use > self.high_watermark:
            self.high_watermark = self.bytes_in_use

    def set_num_chips(self, num_chips: int):
        """Adopt the mesh's chip count (the runner learns it when the
        mesh is built, after the ledger exists) and re-attribute every
        live entry under the new split. Watermarks reset to the current
        totals — a high-watermark against a different chip count is not
        comparable."""
        d = max(1, int(num_chips))
        with self._mu:
            if d == self.num_chips:
                return
            self.num_chips = d
            self._chip_bytes = [0] * d
            self._by_chip_owner = {}
            for k, (nbytes, _fn) in self._entries.items():
                self._account(k, nbytes, +1)
            for k, nbytes in self._inflight.items():
                self._account(k, nbytes, +1)
            self._chip_hwm = list(self._chip_bytes)
            self.high_watermark = max(self.high_watermark,
                                      self.bytes_in_use)

    def register_external(self, owner: str, fn):
        """Register a live per-chip byte reporter folded into
        breakdown() under `owner` (tier-1 cache pins: the ResultCache
        owns those buffers and their eviction policy, so the ledger
        reports rather than manages them). fn(num_chips) -> {chip:
        bytes}."""
        with self._mu:
            self._external[owner] = fn

    def breakdown(self) -> dict:
        """{(chip, owner-class): bytes} — ledger-managed classes
        (table_columns, cube_tables, inflight) plus external reporters
        (cache_pins). The ledger-managed slice sums EXACTLY to
        bytes_in_use; the whole breakdown sums to total_bytes()."""
        with self._mu:
            out = dict(self._by_chip_owner)
            external = dict(self._external)
            d = self.num_chips
        for owner, fn in external.items():
            try:
                per_chip = fn(d) or {}
            except Exception:  # noqa: BLE001 — accounting, not serving
                continue
            for c, nbytes in per_chip.items():
                if nbytes:
                    k = (int(c), owner)
                    out[k] = out.get(k, 0) + int(nbytes)
        return out

    def total_bytes(self) -> int:
        """bytes_in_use plus external (cache-pin) bytes — what
        breakdown() sums to."""
        snap = self.breakdown()
        with self._mu:
            core = self.bytes_in_use
        return core + sum(b for (_c, o), b in snap.items()
                          if o in self._external)

    def watermarks(self) -> dict:
        """Ledger-managed high-watermarks, total and per chip."""
        with self._mu:
            return {"total": self.high_watermark,
                    "per_chip": list(self._chip_hwm)}

    @property
    def inflight_bytes(self) -> int:
        with self._mu:
            return sum(self._inflight.values())

    def touch(self, key):
        with self._mu:
            if key in self._entries:
                self._entries.move_to_end(key)

    def add(self, key, nbytes: int, evict_fn, pinned=frozenset()):
        with self._mu:
            if self.budget is not None:
                for k in list(self._entries):
                    if self.bytes_in_use + nbytes <= self.budget:
                        break
                    if k in pinned:
                        continue
                    n, fn = self._entries.pop(k)
                    self.bytes_in_use -= n
                    self._account(k, n, -1)
                    self.evictions += 1
                    fn()
            self._entries[key] = (nbytes, evict_fn)
            self.bytes_in_use += nbytes
            self._account(key, nbytes, +1)

    def pin_inflight(self, key, nbytes: int):
        """Account a dispatch's not-yet-transferred output buffers:
        counted in bytes_in_use (so later adds evict columns to stay
        within budget) but never in the evictable entry set."""
        with self._mu:
            self._inflight[key] = int(nbytes)
            self.bytes_in_use += int(nbytes)
            self._account(key, int(nbytes), +1)

    def unpin_inflight(self, key):
        with self._mu:
            n = self._inflight.pop(key, None)
            if n is not None:
                self.bytes_in_use -= n
                self._account(key, n, -1)

    def remove(self, key):
        with self._mu:
            e = self._entries.pop(key, None)
            if e is not None:
                self.bytes_in_use -= e[0]
                self._account(key, e[0], -1)

    def remove_table(self, table_name: str):
        with self._mu:
            for k in [k for k in self._entries if k[0] == table_name]:
                self.remove(k)


def narrow_dtype(table: TableSegments, name: str):
    """Smallest int dtype (int8/int16/int32/int64) holding every
    value of a LONG column per the segment manifest's column min/max
    — 2-8x less HBM residency and scan bandwidth; sums still widen
    to the accumulator dtype on device. Usually a no-op cast: ingest
    already stores the narrowed dtype. __time stays int64 (epoch
    millis exceed int32). None for what is not a LONG column: it is
    resident at the dtype it is stored at."""
    if name == TIME_COLUMN or \
            table.schema.get(name) is not ColumnType.LONG:
        return None
    from tpu_olap.segments.ingest import _int_dtype_for
    lo = hi = None
    for s in table.segments:
        mlo = s.meta.column_min.get(name)
        mhi = s.meta.column_max.get(name)
        if mlo is None:
            continue  # empty/all-null segment stores zero fill
        lo = mlo if lo is None else min(lo, mlo)
        hi = mhi if hi is None else max(hi, mhi)
    if lo is None:
        return np.dtype(np.int8)
    return _int_dtype_for(lo, hi)


class DeviceDataset:
    """Lazy per-column device stacks for one table.

    With a mesh, stacks are padded to a multiple of the chip count with
    fully-invalid segments, reordered into the INTERLEAVED placement
    (executor.sharding.placement: logical segment i → chip i mod D, so
    chip c's contiguous NamedSharding block holds its interleaved
    segments) and device_put sharded on the segment axis — every chip
    holds 1/D of each column in its HBM, and any contiguous time range
    of logical segments is load-balanced across all chips.

    Snapshot swaps (real-time appends, incremental compaction) pass the
    superseded dataset as `prev`: resident columns REBASE on device —
    rows of segments shared by identity with the old snapshot are
    gathered from the old device stack, and only delta-touched
    segments' rows upload (the ROADMAP 4c "appendable device buffers"
    fix: a small append no longer re-uploads every column).
    """

    def __init__(self, table: TableSegments, mesh=None,
                 ledger: HbmLedger | None = None, prev=None):
        self.table = table
        self.mesh = mesh
        self.ledger = ledger
        self._cols: dict[str, object] = {}
        self._nulls: dict[str, object] = {}
        self._derived: dict[str, object] = {}
        self._valid = None
        n_seg = len(table.segments)
        self.to_place = self.to_logical = None
        self.n_chips = 1
        if mesh is not None:
            from tpu_olap.executor.sharding import (pad_segments,
                                                    placement)
            self.n_chips = mesh.devices.size
            n_seg = pad_segments(max(n_seg, 1), self.n_chips)
            self.to_place, self.to_logical = placement(n_seg,
                                                       self.n_chips)
        self.shape = (n_seg, table.block_rows)
        # incremental re-place (docs/INGEST.md): snapshot the old
        # dataset's resident stacks + placement so each column can
        # rebase device-side, uploading only changed segments' rows
        self._rebase = None
        self.rebased_cols = 0
        self.rebase_rows_uploaded = 0
        if (prev is not None
                and prev.table is not table
                and prev.table.block_rows == table.block_rows
                and prev.mesh is mesh):
            old_segs = prev.table.segments
            # uid equality, not object identity: incremental compaction
            # re-wraps untouched partitions in fresh Segment shells
            # around the SAME column arrays, carrying the uid over
            changed = [i for i, s in enumerate(table.segments)
                       if i >= len(old_segs)
                       or s.uid != old_segs[i].uid]
            # only worth the gather/scatter when most rows carry over
            if changed and len(changed) * 2 <= len(table.segments):
                self._rebase = {
                    "cols": dict(prev._cols),
                    "nulls": dict(prev._nulls),
                    "valid": prev._valid,
                    "old_place": prev.to_place,
                    "old_n": prev.shape[0],
                    "changed": changed,
                }

    def _put(self, arr: np.ndarray):
        import jax
        if self.mesh is not None:
            from tpu_olap.executor.sharding import shard_put
            return shard_put(arr, self.mesh)
        return jax.device_put(arr)

    def _place_pos(self, logical_ids, old: bool = False) -> np.ndarray:
        """Placed positions of logical segment ids (identity without a
        mesh; the interleave permutation with one)."""
        ids = np.asarray(logical_ids, np.int64)
        perm = self._rebase["old_place"] if old else self.to_place
        if perm is None:
            return ids
        return np.asarray(perm, np.int64)[ids]

    def _rebase_stack(self, old_arr, per_segment, target_dtype):
        """New device stack from the old snapshot's resident stack:
        unchanged segments gather from device memory, changed segments'
        rows upload. None when ineligible (dtype drift, no old stack) —
        the caller falls back to a full _stack + _put."""
        rb = self._rebase
        if rb is None or old_arr is None:
            return None
        if target_dtype is not None and \
                np.dtype(old_arr.dtype) != np.dtype(target_dtype):
            return None  # narrowed dtype widened: full re-upload
        import jax
        import jax.numpy as jnp
        changed = rb["changed"]
        n_new = len(self.table.segments)
        changed_set = set(changed)
        keep = [i for i in range(n_new)
                if i not in changed_set and i < rb["old_n"]]
        fresh = np.stack([per_segment(self.table.segments[i])
                          for i in changed])
        old_pos = self._place_pos(keep, old=True)
        new_pos_keep = self._place_pos(keep)
        new_pos_changed = self._place_pos(changed)
        S_new = self.shape[0]

        def build(old, up):
            base = jnp.zeros((S_new,) + old.shape[1:], old.dtype)
            if keep:
                base = base.at[new_pos_keep].set(old[old_pos])
            # explicit cast: jax promotes scatter values strictly, and a
            # weakly-typed uploaded block must not widen an int8 stack
            return base.at[new_pos_changed].set(up.astype(old.dtype))

        if self.mesh is not None:
            from tpu_olap.executor.sharding import shard_spec
            out = jax.jit(build,
                          out_shardings=shard_spec(self.mesh))(old_arr,
                                                               fresh)
        else:
            out = jax.jit(build)(old_arr, fresh)
        self.rebased_cols += 1
        self.rebase_rows_uploaded += int(fresh.size // max(
            1, self.table.block_rows)) * self.table.block_rows
        return out

    def _stack(self, per_segment, dtype=None) -> np.ndarray:
        rows = [per_segment(s) for s in self.table.segments]
        fill = self.shape[0] - len(rows)
        if fill > 0:
            proto = rows[0] if rows else np.zeros(self.table.block_rows,
                                                  dtype or np.int32)
            rows = rows + [np.zeros_like(proto)] * fill
        out = np.stack(rows)
        if self.to_logical is not None:
            # placement (chip-major) order: placed[p] = logical[tl[p]]
            out = out[self.to_logical]
        return out

    def _narrow_dtype(self, name: str):
        return narrow_dtype(self.table, name)

    def _ledger_add(self, kind: str, name: str, arr, pinned):
        if self.ledger is None:
            return
        key = (self.table.name, kind, name)
        nbytes = int(np.prod(self.shape)) * np.dtype(arr.dtype).itemsize \
            if arr.dtype != bool else int(np.prod(self.shape))
        store = self._cols if kind == "col" else self._nulls
        self.ledger.add(key, nbytes, lambda: store.pop(name, None), pinned)

    def col(self, name: str, pinned=frozenset()):
        if name not in self._cols:
            dt = self._narrow_dtype(name)
            get = (lambda s: s.columns[name]) if dt is None else \
                (lambda s: s.columns[name].astype(dt, copy=False))
            arr = None
            if self._rebase is not None:
                arr = self._rebase_stack(
                    self._rebase["cols"].pop(name, None), get, dt)
            self._cols[name] = arr if arr is not None \
                else self._put(self._stack(get))
            self._ledger_add("col", name, self._cols[name], pinned)
        elif self.ledger is not None:
            self.ledger.touch((self.table.name, "col", name))
        return self._cols[name]

    def null_mask(self, name: str, pinned=frozenset()):
        """None if the column has no nulls anywhere."""
        if name not in self._nulls:
            if any(name in s.null_masks for s in self.table.segments):
                zero = np.zeros(self.table.block_rows, bool)
                get = lambda s: s.null_masks.get(name, zero)  # noqa: E731
                arr = None
                if self._rebase is not None:
                    arr = self._rebase_stack(
                        self._rebase["nulls"].pop(name, None), get, bool)
                self._nulls[name] = arr if arr is not None \
                    else self._put(self._stack(get))
                self._ledger_add("null", name, self._nulls[name], pinned)
            else:
                self._nulls[name] = None
        elif self.ledger is not None and self._nulls[name] is not None:
            self.ledger.touch((self.table.name, "null", name))
        return self._nulls[name]

    def derived(self, token: str, build, pinned=frozenset()):
        """Device-resident derived stream [S, R] (precomputed dim ids:
        remap/timeformat gathers; a filter's translated codes, ranks or
        millis), computed ONCE per content token and reused across
        queries — a per-dispatch 6M-row 1-D gather is ~60 ms on a v5e
        through the XLA lowering; a resident stream costs one HBM read
        like any other column. Ledger-tracked at the built array's bytes
        and evictable; an evicted stream transparently rebuilds. `pinned`
        must carry the in-flight query's working set so this add cannot
        evict buffers the same query is about to use."""
        if token not in self._derived:
            arr = build()
            self._derived[token] = arr
            if self.ledger is not None:
                key = (self.table.name, "derived", token)
                self.ledger.add(key, int(arr.nbytes),
                                lambda: self._derived.pop(token, None),
                                pinned)
        elif self.ledger is not None:
            self.ledger.touch((self.table.name, "derived", token))
        return self._derived[token]

    def valid(self):
        """[S, R] row-validity (padding rows/segments are False).
        Never ledgered: every query needs it and it is 1 byte/row.

        valid() is the LAST rebase consumer of a dispatch's working-set
        build (env() columns first, then validity — see
        QueryRunner._prepare_inner), so the rebase snapshot drops here:
        holding it longer would keep the superseded dataset's entire
        device-resident column set alive UNACCOUNTED (prev.evict()
        already released its ledger entries). Columns first touched by
        a later query pay a full upload instead — the hot columns (the
        ones being queried during ingest) are exactly the first
        dispatch's set."""
        if self._valid is None:
            r = np.arange(self.table.block_rows)
            get = lambda s: r < s.meta.n_valid  # noqa: E731
            arr = None
            if self._rebase is not None:
                arr = self._rebase_stack(self._rebase["valid"], get,
                                         bool)
            self._valid = arr if arr is not None \
                else self._put(self._stack(get, bool))
        self._rebase = None
        return self._valid

    def segment_mask(self, kept_ids) -> np.ndarray:
        """Host-side [S] bool from pruned LOGICAL segment ids (device
        input arg). Under a mesh the mask comes back in PLACEMENT order
        to match the placed column stacks."""
        m = np.zeros(self.shape[0], bool)
        m[list(kept_ids)] = True
        if self.to_logical is not None:
            m = m[self.to_logical]
        return m

    def env(self, columns, null_cols):
        """Build the kernel env for the requested columns. The whole
        working set is pinned while it builds so budget eviction cannot
        drop a column this same query is about to use."""
        pinned = frozenset(
            [(self.table.name, "col", c) for c in columns]
            + [(self.table.name, "null", c) for c in null_cols])
        return {
            "cols": {c: self.col(c, pinned) for c in columns},
            "nulls": {c: m for c in null_cols
                      if (m := self.null_mask(c, pinned)) is not None},
        }

    def resident_bytes(self) -> int:
        """Live device bytes this dataset holds right now: column/null/
        derived stacks plus the validity mask, via each buffer's own
        nbytes (jax Arrays and numpy arrays both expose it) — the
        per-table series behind `tpu_olap_device_bytes{table=...}`.
        list() snapshots tolerate the abandoned-deadline-thread
        concurrency the cache dicts already allow."""
        total = 0
        for store in (self._cols, self._nulls, self._derived):
            for arr in list(store.values()):
                total += int(getattr(arr, "nbytes", 0) or 0)
        if self._valid is not None:
            total += int(getattr(self._valid, "nbytes", 0) or 0)
        return total

    def evict(self):
        self._cols.clear()
        self._nulls.clear()
        self._derived.clear()
        self._valid = None
        self._rebase = None
        if self.ledger is not None:
            self.ledger.remove_table(self.table.name)
