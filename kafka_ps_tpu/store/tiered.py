"""TieredParamStore — hot/warm/cold residency for one theta slice.

The server's parameter slice is split into fixed-size PAGES (contiguous
key ranges).  Each page lives in exactly one tier at a time:

  hot   device-resident f32 array (compress/slab.ParamPageSlab — the
        PR 6 device slab, per-page instead of full-slice);
  warm  pinned host-RAM f32 array;
  cold  one CRC-framed record in the durable commit log, addressed by
        offset (store/cold.ColdStore over CommitLog.read_at).

Per-page heat (reads via `pin`, delta writes via `update_page`) drives
promotion/demotion on a background policy thread; heat is exported as
the `param_range_heat` telemetry family.  The capacity story: the hot
(and optionally warm) byte budgets cap what is resident, everything
else is a log record — models outgrow HBM, then host RAM
(docs/TIERING.md, ROADMAP item 5).

Correctness contract — residency NEVER changes values:

  * pages are replaced wholesale, never mutated in place (the theta
    replacement contract, runtime/server.py docstring), so any thread
    may keep using a value reference it obtained earlier;
  * a migration moves bits verbatim between tiers (device_put / host
    fetch / log append+read of the same f32 bytes), so which tier a
    page occupies is invisible to every computation — the bitwise-
    equality bar (capped run == fully resident run, scripts/tier1.sh
    --tier) holds no matter when the policy thread runs;
  * residency decisions themselves are deterministic pure functions of
    the heat counters (sort by (-heat, page index)); only their TIMING
    depends on the thread scheduler, and timing cannot reach replay
    because of the point above.

Locking discipline (analysis/lockgraph, PS105): one leaf
`store.residency` OrderedLock guards the residency table.  Blocking
work — log appends/point reads, device transfers, host fetches — runs
OUTSIDE the lock: a migration snapshots (value, version) under the
lock, does its I/O unlocked, then re-acquires and commits only if the
page's version is unchanged (a racing write wins; the abandoned cold
record is benign append-only garbage).  Writes land hot or warm only,
so `update_page` never touches the log.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from kafka_ps_tpu.analysis.lockgraph import OrderedLock
from kafka_ps_tpu.runtime.messages import KeyRange
from kafka_ps_tpu.telemetry.flight import FLIGHT

TIER_HOT, TIER_WARM, TIER_COLD = 0, 1, 2
TIER_NAMES = ("hot", "warm", "cold")


class _Page:
    """Residency record for one key range.  `value` is a device array
    (hot), a host f32 array (warm), or None (cold — `cold_offset` then
    addresses the log record).  `version` counts value replacements;
    migrations commit only against an unchanged version."""

    __slots__ = ("index", "start", "end", "tier", "value", "cold_offset",
                 "version", "reads", "writes")

    def __init__(self, index: int, start: int, end: int,
                 value: np.ndarray):
        self.index = index
        self.start = start
        self.end = end
        self.tier = TIER_WARM
        self.value = value
        self.cold_offset = -1
        self.version = 0
        self.reads = 0
        self.writes = 0

    @property
    def nbytes(self) -> int:
        return (self.end - self.start) * 4

    @property
    def heat(self) -> int:
        return self.reads + self.writes


class TieredParamStore:
    """Paged hot/warm/cold store for one server's theta slice."""

    def __init__(self, values: np.ndarray, key_range: KeyRange, *,
                 hot_bytes: int = 0, warm_bytes: int = 0,
                 page_params: int = 1024, cold=None, telemetry=None,
                 rebalance_interval_s: float = 0.05):
        from kafka_ps_tpu.compress.slab import ParamPageSlab
        if telemetry is None:
            from kafka_ps_tpu.telemetry import NULL_TELEMETRY
            telemetry = NULL_TELEMETRY
        if page_params <= 0:
            raise ValueError("page_params must be positive")
        if warm_bytes > 0 and cold is None:
            raise ValueError(
                "a warm-tier cap needs a cold store to overflow into "
                "(pass cold=ColdStore.open(...) or run under "
                "--durable-log)")
        self.key_range = key_range
        self.page_params = page_params
        # 0 = unbounded (the "today's behavior" default, ISSUE 13)
        self.hot_budget = hot_bytes if hot_bytes > 0 else None
        self.warm_budget = warm_bytes if warm_bytes > 0 else None
        self.cold = cold
        self.telemetry = telemetry
        self.rebalance_interval_s = rebalance_interval_s
        self._slab = ParamPageSlab()
        self._lock = OrderedLock("store.residency")
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

        vals = np.ascontiguousarray(np.asarray(values), dtype=np.float32)
        if vals.shape != (key_range.end - key_range.start,):
            raise ValueError(
                f"values shape {vals.shape} != key range "
                f"[{key_range.start}, {key_range.end})")
        self._pages: list[_Page] = []
        for i, lo in enumerate(range(key_range.start, key_range.end,
                                     page_params)):
            hi = min(lo + page_params, key_range.end)
            self._pages.append(_Page(
                i, lo, hi,
                vals[lo - key_range.start:hi - key_range.start].copy()))

        # measured counters `stats()` reads (host ints, no device
        # sync anywhere near them)
        self.pins = {"hot": 0, "warm": 0, "cold": 0}
        # guarded-by: _lock (rebalance writes hold the residency lock; stats reads are snapshots)
        self.promotions = 0
        # guarded-by: _lock (rebalance writes hold the residency lock; stats reads are snapshots)
        self.demotions = 0
        self.faults = 0          # cold pages materialized on demand
        # guarded-by: _lock (rebalance writes hold the residency lock; stats reads are snapshots)
        self.rebalances = 0
        self._m_pins = {t: telemetry.counter("param_tier_pins_total",
                                             tier=t)
                        for t in TIER_NAMES}
        self._m_migrations = {
            d: telemetry.counter("param_tier_migrations_total",
                                 direction=d)
            for d in ("promote", "demote")}
        self._m_migration_ms = {
            d: telemetry.histogram("param_tier_migration_ms", direction=d)
            for d in ("promote", "demote")}
        self.rebalance()         # settle the initial residency

    # -- page geometry -------------------------------------------------------

    @property
    def num_pages(self) -> int:
        return len(self._pages)

    def pages_overlapping(self, key_range: KeyRange) -> range:
        """Indices of pages intersecting [start, end)."""
        start = max(key_range.start, self.key_range.start)
        end = min(key_range.end, self.key_range.end)
        if end <= start:
            return range(0)
        first = (start - self.key_range.start) // self.page_params
        last = (end - 1 - self.key_range.start) // self.page_params
        return range(first, last + 1)

    def page_range(self, index: int) -> KeyRange:
        p = self._pages[index]
        return KeyRange(p.start, p.end)

    # -- reads ---------------------------------------------------------------

    def pin_pages(self, key_range: KeyRange, count_heat: bool = True):
        """Materialize every page overlapping `key_range`:
        [(page index, KeyRange, value)] with value a device array for
        hot pages and a host f32 array for warm/cold (cold pages are
        faulted in from the log and installed warm).  Counts read heat
        and per-tier pin hits unless `count_heat` is False."""
        touched = self.pages_overlapping(key_range)
        out = []
        faults = []              # (page, offset, version)
        with self._lock:
            for i in touched:
                p = self._pages[i]
                if count_heat:
                    p.reads += 1
                    tier = TIER_NAMES[p.tier]
                    self.pins[tier] += 1
                    if self.telemetry.enabled:
                        self._m_pins[tier].inc()
                if p.tier == TIER_COLD:
                    faults.append((p, p.cold_offset, p.version))
                    out.append([i, KeyRange(p.start, p.end), None])
                else:
                    out.append([i, KeyRange(p.start, p.end), p.value])
        if faults:
            # log point reads happen OUTSIDE the residency lock
            t0 = time.perf_counter()
            fetched = [(p, ver,
                        self.cold.get(off, p.index, p.start, p.end))
                       for p, off, ver in faults]
            dt_ms = (time.perf_counter() - t0) * 1e3
            by_index = {}
            with self._lock:
                for p, ver, vals in fetched:
                    if p.tier == TIER_COLD and p.version == ver:
                        # install warm: the VALUE is unchanged, so the
                        # version is not bumped — a concurrent migration
                        # of this page would be a no-op anyway
                        p.tier = TIER_WARM
                        p.value = vals
                        p.cold_offset = -1
                        self.faults += 1
                        self.promotions += 1
                        by_index[p.index] = p.value
                    else:
                        # a racing write already landed the page warm/
                        # hot with a NEWER value; use that
                        by_index[p.index] = p.value
            if self.telemetry.enabled:
                self._m_migrations["promote"].inc(len(fetched))
                self._m_migration_ms["promote"].observe(dt_ms)
            if FLIGHT.enabled:
                # demand faults are the tail-latency event a postmortem
                # wants on the timeline: which pages, how long
                FLIGHT.record("store.fault", pages=len(fetched),
                              ms=round(dt_ms, 3))
            for entry in out:
                if entry[2] is None:
                    entry[2] = by_index[entry[0]]
        return [tuple(e) for e in out]

    def pin(self, key_range: KeyRange, count_heat: bool = True
            ) -> np.ndarray:
        """Host f32 vector for exactly [start, end) — the on-demand
        range pull ShardRouter/WeightsAssembler and the serving
        snapshot path use (docs/TIERING.md)."""
        pages = self.pin_pages(key_range, count_heat=count_heat)
        start = max(key_range.start, self.key_range.start)
        end = min(key_range.end, self.key_range.end)
        out = np.empty(end - start, dtype=np.float32)
        for _, kr, value in pages:
            host = value if isinstance(value, np.ndarray) \
                else np.asarray(value, dtype=np.float32)
            lo, hi = max(kr.start, start), min(kr.end, end)
            out[lo - start:hi - start] = host[lo - kr.start:hi - kr.start]
        return out

    def assembled(self) -> np.ndarray:
        """Full-slice host vector WITHOUT heat accounting — the eval/
        checkpoint/snapshot peek (reading the whole slice must not
        convince the policy everything is equally hot)."""
        return self.pin(self.key_range, count_heat=False)

    # -- writes --------------------------------------------------------------

    def update_page(self, index: int, values) -> None:
        """Replace one page's value (a delta apply's output).  Device
        arrays stay device-resident when the page is hot; writes to a
        warm or cold page land warm (never a log append — blocking log
        I/O is the policy thread's job, outside this hot path)."""
        p = self._pages[index]
        prepared = values
        while True:
            if isinstance(prepared, np.ndarray):
                prepared = np.ascontiguousarray(prepared,
                                                dtype=np.float32)
            with self._lock:
                is_host = isinstance(prepared, np.ndarray)
                if p.tier == TIER_HOT:
                    p.value = self._slab.put(index, prepared)
                elif is_host:
                    if p.tier == TIER_COLD:
                        p.tier = TIER_WARM
                        p.cold_offset = -1
                    p.value = prepared
                else:
                    # device value but the page is not hot (the policy
                    # thread demoted it mid-flight): fetch to host
                    # OUTSIDE the lock and retry
                    pass
                if p.tier == TIER_HOT or is_host:
                    p.version += 1
                    p.writes += 1
                    return
            prepared = np.asarray(prepared, dtype=np.float32)

    def replace_all(self, values) -> None:
        """Scatter a full slice into the pages, preserving residency
        where possible (cold pages land warm; the policy re-demotes) —
        the theta-setter path: checkpoint restore, fused loops."""
        vals = np.ascontiguousarray(np.asarray(values), dtype=np.float32)
        if vals.shape != (self.key_range.end - self.key_range.start,):
            raise ValueError(f"replace_all shape {vals.shape}")
        base = self.key_range.start
        with self._lock:
            for p in self._pages:
                chunk = vals[p.start - base:p.end - base].copy()
                p.version += 1
                p.writes += 1
                if p.tier == TIER_HOT:
                    p.value = self._slab.put(p.index, chunk)
                else:
                    if p.tier == TIER_COLD:
                        p.tier = TIER_WARM
                        p.cold_offset = -1
                    p.value = chunk

    # -- the policy ----------------------------------------------------------

    def _plan_locked(self) -> dict[int, int]:
        """Deterministic target residency from the heat counters: pages
        ordered by (-heat, index), greedily assigned hot until the hot
        budget, then warm until the warm budget, then cold.  Pure
        function of the counters — no clocks, no randomness (PS104)."""
        order = sorted(self._pages, key=lambda p: (-p.heat, p.index))
        targets: dict[int, int] = {}
        hot_left = self.hot_budget
        warm_left = self.warm_budget
        for p in order:
            if hot_left is None or p.nbytes <= hot_left:
                targets[p.index] = TIER_HOT
                if hot_left is not None:
                    hot_left -= p.nbytes
            elif self.cold is None or warm_left is None \
                    or p.nbytes <= warm_left:
                targets[p.index] = TIER_WARM
                if warm_left is not None:
                    warm_left = max(warm_left - p.nbytes, 0)
            else:
                targets[p.index] = TIER_COLD
        return targets

    def rebalance(self) -> dict:
        """One policy pass: compute the deterministic target residency,
        migrate the diff (I/O outside the lock, version-checked
        commit), decay the heat counters, export heat gauges."""
        with self._lock:
            targets = self._plan_locked()
            moves = [(p, targets[p.index], p.value, p.cold_offset,
                      p.version)
                     for p in self._pages if p.tier != targets[p.index]]
        applied = self._migrate(moves)
        with self._lock:
            self.rebalances += 1
            for p in self._pages:
                # exponential heat decay so the policy tracks access
                # SHIFTS, not lifetime totals; integer halving keeps
                # the counters (and the plan) deterministic
                p.reads //= 2
                p.writes //= 2
            if self.telemetry.enabled:
                for p in self._pages:
                    rng = f"{p.start}:{p.end}"
                    self.telemetry.gauge("param_range_heat", kind="read",
                                         range=rng).set(p.reads)
                    self.telemetry.gauge("param_range_heat", kind="write",
                                         range=rng).set(p.writes)
                counts = [0, 0, 0]
                for p in self._pages:
                    counts[p.tier] += 1
                for t, n in zip(TIER_NAMES, counts):
                    self.telemetry.gauge("param_tier_pages",
                                         tier=t).set(n)
        return {"moved": applied, "targets": len(moves)}

    def _migrate(self, moves) -> int:
        """Apply (page, target tier) moves: blocking work (host fetch,
        log append, log read, device upload) runs with the lock
        RELEASED; each commit re-checks the page's version so a racing
        `update_page` always wins."""
        applied = 0
        for p, target, value, cold_offset, version in moves:
            promote = target < p.tier
            t0 = time.perf_counter()
            # --- unlocked I/O: produce the target-tier value form ----
            if target == TIER_COLD:
                host = value if isinstance(value, np.ndarray) \
                    else np.asarray(value, dtype=np.float32)
                new_offset = self.cold.put(p.index, p.start, p.end, host)
                new_value = None
            elif target == TIER_WARM:
                if value is None:       # cold -> warm: point read
                    new_value = self.cold.get(cold_offset, p.index,
                                              p.start, p.end)
                else:
                    new_value = value if isinstance(value, np.ndarray) \
                        else np.asarray(value, dtype=np.float32)
                new_offset = -1
            else:                       # -> hot: device upload
                if value is None:
                    value = self.cold.get(cold_offset, p.index,
                                          p.start, p.end)
                new_value = self._slab.put(p.index, value)
                new_offset = -1
            # --- locked commit, version-checked ----------------------
            with self._lock:
                if p.version != version:
                    # a write replaced the value mid-migration: abandon
                    # (an appended cold record becomes benign garbage)
                    if target == TIER_HOT and p.tier != TIER_HOT:
                        self._slab.drop(p.index)
                    continue
                if p.tier == TIER_HOT and target != TIER_HOT:
                    self._slab.drop(p.index)
                p.tier = target
                p.value = new_value
                p.cold_offset = new_offset
                applied += 1
                if promote:
                    self.promotions += 1
                else:
                    self.demotions += 1
            dt_ms = (time.perf_counter() - t0) * 1e3
            d = "promote" if promote else "demote"
            if self.telemetry.enabled:
                self._m_migrations[d].inc()
                self._m_migration_ms[d].observe(dt_ms)
            if FLIGHT.enabled:
                FLIGHT.record(f"store.{d}", page=p.index,
                              tier=TIER_NAMES[target],
                              ms=round(dt_ms, 3))
        return applied

    # -- the background policy thread ---------------------------------------

    def start_policy_thread(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.rebalance_interval_s):
                self.rebalance()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="kps-tier-policy")
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=10.0)
        self._thread = None
        if self.cold is not None:
            self.cold.close()

    # -- checkpoint surface --------------------------------------------------

    def residency_vector(self) -> np.ndarray:
        with self._lock:
            return np.array([p.tier for p in self._pages], dtype=np.int8)

    def heat_vectors(self) -> tuple[np.ndarray, np.ndarray]:
        with self._lock:
            return (np.array([p.reads for p in self._pages], np.int64),
                    np.array([p.writes for p in self._pages], np.int64))

    def set_residency(self, tiers, reads=None, writes=None) -> None:
        """Restore recorded residency + heat (utils/checkpoint.py),
        AFTER `replace_all` put the restored values in place.  Recorded-
        cold pages are RE-demoted with fresh log appends — the
        checkpoint stays self-contained and never references records a
        crash may have torn off the log tail."""
        tiers = np.asarray(tiers)
        if len(tiers) != len(self._pages):
            raise ValueError(
                f"residency vector has {len(tiers)} pages, store has "
                f"{len(self._pages)} — page_params changed across "
                "restore?")
        with self._lock:
            if reads is not None:
                for p, r in zip(self._pages, np.asarray(reads)):
                    p.reads = int(r)
            if writes is not None:
                for p, w in zip(self._pages, np.asarray(writes)):
                    p.writes = int(w)
            moves = [(p, int(t), p.value, p.cold_offset, p.version)
                     for p, t in zip(self._pages, tiers)
                     if p.tier != int(t)]
        self._migrate(moves)

    # -- accounting ----------------------------------------------------------

    def resident_bytes(self) -> dict:
        with self._lock:
            hot = sum(p.nbytes for p in self._pages
                      if p.tier == TIER_HOT)
            warm = sum(p.nbytes for p in self._pages
                       if p.tier == TIER_WARM)
            cold = sum(p.nbytes for p in self._pages
                       if p.tier == TIER_COLD)
        return {"hot": hot, "warm": warm, "cold_logged": cold,
                "resident": hot + warm,
                "total": sum(p.nbytes for p in self._pages)}

    def tier_counts(self) -> dict:
        with self._lock:
            counts = [0, 0, 0]
            for p in self._pages:
                counts[p.tier] += 1
        return dict(zip(TIER_NAMES, counts))

    def stats(self) -> dict:
        total_pins = sum(self.pins.values()) or 1
        return {
            "pages": self.num_pages,
            "page_params": self.page_params,
            "tiers": self.tier_counts(),
            "pins": dict(self.pins),
            "hit_rate": {t: round(self.pins[t] / total_pins, 4)
                         for t in TIER_NAMES},
            "promotions": self.promotions,
            "demotions": self.demotions,
            "faults": self.faults,
            "rebalances": self.rebalances,
            "resident_bytes": self.resident_bytes(),
            "device_bytes": self._slab.device_bytes(),
            "upload_bytes": self._slab.bytes_uploaded,
        }
