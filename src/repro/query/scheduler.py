"""BatchScheduler: admission, shape-grouped batching, and stats/projection.

The serving loop of FlashQL: clients ``submit`` queries (tickets), and
``flush`` compiles the pending set through the plan cache and executes it
as ONE fused device program per flush signature
(:func:`repro.query.compile.compile_flush`): every predicate signature
group senses under ``jax.vmap``, the results feed every aggregate's
(weighted-)popcount reduce device-side, and the whole flush returns as a
single flat payload — one kernel dispatch and one host transfer per
flush, whatever mix of aggregate kinds it holds (counted in
``host_transfers`` / ``fused_dispatches`` and asserted in tests).
Devices holding non-ESP pages (whose reads inject modelled bit errors)
fall back to the per-group legacy path: vmap batches via
:class:`FlashDevice.execute_batch`, then one reduce dispatch + one
transfer per reduce signature (:func:`repro.query.aggregate.reduce_flush`).

Every stat the scheduler keeps lives in one
:class:`repro.query.telemetry.Telemetry` registry: the legacy counter
attributes (``host_transfers``, ``wordlines_sensed``, …) are read-only
views over it, ``stats()`` is reimplemented on top (bit-compatible,
asserted in tests), and — when telemetry is enabled — every flush records
its lifecycle (compile -> dispatch -> transfer -> reduce) as trace spans,
every result carries a sensing + latency attribution, and tickets past a
latency/sensing threshold land in the slow-query log.

The scheduler also records every executed MWS command's shape
(:class:`repro.flashsim.workloads.MWSCommandShape`), so ``projection()``
can replay the served traffic through the paper's full-scale SSD model and
report projected wall-clock time and energy on real NAND-flash hardware
(Table-1 geometry), next to the OSP baseline.
"""

from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.commands import MWSCommand, ThresholdCommand
from repro.flashsim.geometry import DEFAULT_SSD, SSDConfig
from repro.flashsim.platforms import Platform, run_workload
from repro.flashsim.timing import level_program_factor, level_read_factor
from repro.flashsim.workloads import BulkBitwiseWorkload, MWSCommandShape
from repro.query.aggregate import (
    get_aggregator,
    reduce_flush,
    validate_query,
)
from repro.query.ast import Count, Mask, Query, normalize_agg
from repro.query.bitmap import BitmapStore
from repro.query.compile import QueryCompiler, compile_flush
from repro.query.device import FlashDevice, age_spill_blocks
from repro.query.optimize import cse_flush
from repro.query.telemetry import (
    TID_FLUSH,
    TID_TICKETS,
    Telemetry,
)

# one extra sensed plane (a BSI slice / equality bitmap read for an
# aggregate) = one single-wordline sensing in the SSD projection
AGG_READ_SHAPE = MWSCommandShape(n_blocks=1, max_wls_per_block=1)


def merge_appends(batches: list[dict]) -> dict:
    """Concatenate queued append batches into one combined batch."""
    return {
        col: np.concatenate([b[col] for b in batches])
        for col in batches[0]
    }


def queue_append(store, buf: list[dict], rows: dict) -> None:
    """Validate + queue one append batch for coalesced programming.

    Shared by both schedulers' ``coalesce_appends`` paths so the subtle
    ordering stays in one place: THIS batch's column set is validated
    first (the merge below is built from the first queued batch's
    columns, so an unknown or missing column would slip through it), then
    the cumulative concatenation must fit the schema and capacity BEFORE
    the batch is accepted — ``apply_appends`` can never fail halfway.
    Empty batches validate but queue nothing (an empty ndarray defaults
    to float64 and would poison the merged integer dtype).

    The cumulative check re-merges the queue, O(queued rows) per append:
    exactness is the point — stripe-key routing is data-dependent, so a
    cheaper running row count could admit a stream that overflows one
    stripe at apply time.  Flush boundaries bound the queue length.
    """
    arrays = {c: np.asarray(v) for c, v in rows.items()}
    store.check_append(arrays)
    store.check_append(merge_appends(buf + [arrays]))
    if len(next(iter(arrays.values()))):
        buf.append(arrays)


def plan_traffic(plan) -> tuple[tuple, int]:
    """A plan's projected-traffic contribution, memoized on the plan.

    Walking every MWS command's wordline bitmaps per flush dominated warm
    serving (it was ~2/3 of a steady-state sharded flush in profiles);
    plans are cached and immutable, so their ``(shape counts, wordlines)``
    is computed once and pinned on the instance.
    """
    memo = getattr(plan, "_traffic_memo", None)
    if memo is None:
        shapes: Counter = Counter()
        wls = 0
        for cmd in plan.commands:
            if isinstance(cmd, MWSCommand):
                shapes[
                    MWSCommandShape(
                        n_blocks=cmd.num_blocks,
                        max_wls_per_block=max(
                            len(t.wordlines) for t in cmd.targets
                        ),
                        threshold_k=cmd.k
                        if isinstance(cmd, ThresholdCommand)
                        else 0,
                    )
                ] += 1
                wls += cmd.num_wordlines
        memo = (tuple(shapes.items()), wls)
        plan._traffic_memo = memo
    return memo


def record_plan_traffic(counter: Counter, plan) -> int:
    """Fold a plan's MWS commands into a shape counter; returns wordlines.

    Shape counts keep long-running traffic O(distinct shapes); wordlines are
    tracked exactly because ragged commands pad to ``max_wls_per_block`` and
    must not inflate operand counts in the projection.
    """
    shapes, wls = plan_traffic(plan)
    for shape, cnt in shapes:
        counter[shape] += cnt
    return wls


def plan_sensings(plan) -> int:
    """MWS sensing operations a plan performs (memoized via plan_traffic)."""
    shapes, _ = plan_traffic(plan)
    return sum(cnt for _, cnt in shapes)


def plan_thresholds(plan) -> int:
    """k-of-N threshold sensings in a plan (memoized via plan_traffic)."""
    shapes, _ = plan_traffic(plan)
    return sum(cnt for shape, cnt in shapes if shape.threshold_k)


def attribute_result(
    tele: Telemetry,
    ticket: int,
    query: Query,
    attr: dict | None,
    t_submit: float,
    t_end: float,
    flush: int | None = None,
) -> None:
    """Shared per-result telemetry: ticket trace span, latency histogram,
    and the slow-query log.  Called only when telemetry is enabled.
    ``flush`` is the id of the flush that served the ticket, the id its
    stage spans carry."""
    latency = t_end - t_submit
    tele.observe("query_latency_s", latency)
    sensings = attr["sensings"] if attr else 0
    args = {"ticket": ticket, "sensings": sensings}
    if flush is not None:
        args["flush"] = flush
    tele.span(
        "ticket",
        "query",
        t_submit,
        t_end,
        tid=TID_TICKETS,
        args=args,
    )
    tele.slow(
        {
            "ticket": ticket,
            "predicate": repr(query.where),
            "agg": repr(query.agg),
            "latency_s": latency,
            "attribution": attr,
        },
        latency,
        sensings,
    )


def project_traffic(
    command_shape_counts: Counter,
    *,
    wordlines_sensed: int,
    num_rows: int,
    num_queries: int,
    host_postprocess: bool,
    esp_programs: int = 0,
    block_erases: int = 0,
    levels: int = 1,
    ssd: SSDConfig = DEFAULT_SSD,
    name: str = "flashql",
) -> dict:
    """Project served MWS traffic onto the paper's SSD timing/energy model.

    One call models one device (chip); a sharded fleet projects each
    device's traffic separately and aggregates — time as the max over
    concurrently-serving devices, energy as the sum (see
    ``repro.query.shard``).

    ``esp_programs`` counts the *delta* page programs incremental appends
    issued — only the pages an update actually touched, never a full
    reprogram of the index.  They are charged at ``t_esp_us`` on the
    Flash-Cosmos side (ESP reliability costs ~2x a plain SLC program) and
    at ``t_prog_slc_us`` for the OSP baseline, which rewrites the same
    pages through the ordinary program path.

    ``block_erases`` counts whole-block erases (compaction rebuilds): both
    platforms pay ``t_bers_ms`` per block — garbage collection is the same
    erase-before-program dance wherever the data is computed on.

    ``levels`` is the multi-level packing factor (``Layout.levels``): both
    platforms sense L-level pages through a longer reference staircase
    (``level_read_factor``) and program them with finer ISPP verify steps
    (``level_program_factor``).  What makes packing a *win* is that the
    traffic counts themselves shrink — fewer physical programs/erases for
    the same logical pages — which the callers already fold in before
    projecting.
    """
    if not command_shape_counts and not esp_programs and not block_erases:
        raise ValueError("no traffic served yet")
    if levels > 1:
        ssd = replace(ssd, t_r_us=ssd.t_r_us * level_read_factor(levels))
    wl = BulkBitwiseWorkload(
        name=name,
        num_operands=wordlines_sensed,
        operand_bits=num_rows,
        # a program-only projection (appends landed on a stripe that never
        # sensed) streams no result bitmaps out
        result_bits=num_rows * (num_queries if command_shape_counts else 0),
        num_queries=1,  # shape counts already cover ALL served queries
        host_postprocess=host_postprocess,
        fc_command_counts=tuple(command_shape_counts.items()),
        fc_sensing_ops=sum(command_shape_counts.values()),
    )
    fc = run_workload(wl, Platform.FC, ssd)
    osp = run_workload(wl, Platform.OSP, ssd)
    prog_scale = level_program_factor(levels)
    t_esp = esp_programs * ssd.t_esp_us * prog_scale * 1e-6
    t_prog_osp = esp_programs * ssd.t_prog_slc_us * prog_scale * 1e-6
    t_erase = block_erases * ssd.t_bers_ms * 1e-3
    fc_time = fc.time_s + t_esp + t_erase
    osp_time = osp.time_s + t_prog_osp + t_erase
    fc_energy = fc.energy_j + (t_esp + t_erase) * ssd.p_prog_w
    osp_energy = osp.energy_j + (t_prog_osp + t_erase) * ssd.p_prog_w
    return {
        "workload": wl.name,
        "fc_time_s": fc_time,
        "fc_energy_j": fc_energy,
        "osp_time_s": osp_time,
        "osp_energy_j": osp_energy,
        "esp_programs": esp_programs,
        "block_erases": block_erases,
        "speedup_vs_osp": osp_time / fc_time,
        "energy_ratio_vs_osp": osp_energy / fc_energy,
    }


@dataclass(frozen=True)
class QueryResult:
    ticket: int
    query: Query
    value: object  # the aggregate's final value (int, float, BitVector, …)
    latency_s: float
    cache_hit: bool
    # per-query sensing + latency attribution (None when telemetry is
    # disabled): sensings / wordlines / spill_steps / agg_plane_reads are
    # exact per query; the *_s phase durations are the enclosing flush's
    # lifecycle (shared flush work — a batch amortizes it)
    attribution: dict | None = None

    # legacy accessors: COUNT/MASK callers predate the aggregate pipeline
    @property
    def count(self) -> int | None:
        spec = normalize_agg(self.query.agg)
        return self.value if isinstance(spec, Count) else None

    @property
    def mask(self):
        spec = normalize_agg(self.query.agg)
        return self.value if isinstance(spec, Mask) else None


# legacy counter attributes of the schedulers, reimplemented as read-only
# views over the telemetry registry (one source of truth; stats() stays
# bit-compatible — asserted in tests/test_query_telemetry.py)
def registry_counters(cls, names: tuple[str, ...]):
    for name in names:
        setattr(
            cls,
            name,
            property(lambda self, _n=name: self.telemetry.value(_n)),
        )
    return cls


@dataclass
class BatchScheduler:
    device: FlashDevice
    store: BitmapStore
    max_batch: int = 256
    compiler: QueryCompiler = None  # type: ignore[assignment]
    # one fused device program + ONE host transfer per flush (the default
    # serving path); False keeps the per-reduce-group legacy path — the
    # oracle the differential harness compares against
    fuse_flush: bool = True
    # queue small append() batches and program them as one coalesced delta
    # per touched page on the next flush (or apply_appends())
    coalesce_appends: bool = False
    # -- the cost-based multi-query optimizer (repro.query.optimize) --------
    # canonicalize predicates, pick chain orderings by the flashsim cost
    # model, dedup + CSE-share plans within each fused flush, and
    # materialize hot predicates; False serves exactly as before (the
    # optimizer-off baseline the Zipfian benchmark compares against)
    optimize: bool = True
    # compiles of one canonical predicate before its result bitmap is
    # ESP-programmed as a cached page (see QueryCompiler.materialize);
    # None disables materialization while keeping the other stages
    materialize_after: int | None = 32
    # -- background-compaction policy (see compact()) -----------------------
    # auto-compact when the stripe's tombstone density crosses this (None
    # disables the policy; compact() stays available explicitly).  Checked
    # at mutation boundaries — after delete()/update()/apply_appends() —
    # never mid-flush, so no ticket ever spans a rebuild.
    compact_density: float | None = None
    # on append overflow, rebuild into wider pages (capacity growth folded
    # into the compaction path) instead of rejecting the batch
    grow_on_overflow: bool = False
    # the unified metrics registry + trace recorder; pass
    # Telemetry(enabled=False) to strip every per-event recorder off the
    # hot path (counters keep counting — stats()/projection read them)
    telemetry: Telemetry = None  # type: ignore[assignment]

    _pending: list[tuple[int, Query, float]] = field(default_factory=list)
    _next_ticket: int = 0
    # executed traffic, aggregated per command shape (bounded memory even
    # for a long-running service); wordlines tracked exactly because ragged
    # commands pad to max_wls_per_block and must not inflate operand counts
    command_shape_counts: Counter = field(default_factory=Counter)
    _host_postprocess: bool = False
    # stacked extra sensed planes (BSI slices / equality bitmaps) per
    # (store epoch, page tuple) — see repro.query.aggregate.reduce_flush
    _extras_cache: dict = field(default_factory=dict, repr=False)
    # device-resident valid-row word mask, memoized per ingest epoch
    _mask_cache: tuple | None = field(default=None, repr=False)
    # fused flush programs per (batch composition, store epochs) and their
    # jitted runners per flush signature — see compile_flush
    _flush_programs: dict = field(default_factory=dict, repr=False)
    _runner_cache: dict = field(default_factory=dict, repr=False)
    # the fused program the last flush ran (FlushProgram.lower exposes its
    # HLO and memory analysis); None until a fused flush has run
    last_program: object = field(default=None, repr=False)
    # flush-level CSE rewrites per (batch composition, store epochs) —
    # see repro.query.optimize.cse_flush
    _cse_cache: dict = field(default_factory=dict, repr=False)
    # queued (validated) append batches awaiting coalesced programming
    _append_buf: list = field(default_factory=list, repr=False)

    def __post_init__(self):
        if self.telemetry is None:
            self.telemetry = Telemetry()
        if self.compiler is None:
            self.compiler = QueryCompiler(self.store, self.device)
        self.compiler.telemetry = self.telemetry
        self.compiler.optimize = self.optimize
        self.compiler.materialize_after = (
            self.materialize_after if self.optimize else None
        )
        self.device.telemetry = self.telemetry
        self.telemetry.name_tid(TID_FLUSH, "flush")
        self.telemetry.name_tid(TID_TICKETS, "tickets")
        self.telemetry.providers.setdefault("plan_cache", self._plan_cache)
        self.telemetry.providers.setdefault("projection", self.projection)
        self.telemetry.providers.setdefault(
            "optimizer", self._optimizer_stats
        )

    def _plan_cache(self) -> dict:
        return {
            "hits": self.compiler.hits,
            "misses": self.compiler.misses,
            "size": self.compiler.cache_size,
        }

    def _optimizer_stats(self) -> dict:
        tele = self.telemetry
        served = int(self.queries_served)
        mws = sum(self.command_shape_counts.values())
        return {
            "enabled": self.optimize,
            "sensings_per_query": (mws / served) if served else None,
            "cse_plan_hits": int(tele.value("cse_plan_hits")),
            "cse_shared_senses": int(tele.value("cse_shared_senses")),
            "cse_rewritten_members": int(
                tele.value("cse_rewritten_members")
            ),
            "materializations": int(tele.value("materializations")),
            "materialization_hits": int(
                tele.value("materialization_hits")
            ),
            "materialization_invalidations": int(
                tele.value("materialization_invalidations")
            ),
        }

    def _materialize_hot(self) -> None:
        """Materialization policy: at each flush boundary, ESP-program the
        result bitmaps of predicates past the compiler's heat threshold.
        The build's one sensing pass + page program are charged to traffic
        (the payoff is every later compile lowering to ``mat AND valid``)."""
        if not self.optimize:
            return
        for key, canon in self.compiler.hot_preds():
            plan = self.compiler.materialize(key, canon)
            if plan is not None:
                self.telemetry.count(
                    "wordlines_sensed",
                    record_plan_traffic(self.command_shape_counts, plan),
                )
                thr = plan_thresholds(plan)
                if thr:
                    self.telemetry.count("threshold_senses", thr)
                self.telemetry.count("materialization_programs")

    # -- incremental ingest --------------------------------------------------
    def append(self, rows: dict[str, object]) -> int:
        """Append rows to the live index; returns pages ESP-programmed.

        The whole batch is validated against the ingest schema (exact
        column set, equal lengths, non-negative values, word capacity)
        *before* any page state mutates, and appends are rejected while
        queries are pending — a half-applied batch could otherwise serve a
        flush from a torn index.  Only delta pages are programmed: the
        tail words of pages the new rows actually set, plus fresh pages
        for first-seen values / grown BSI widths.  Plans over columns
        whose index metadata did not change stay warm in the plan cache.

        With ``coalesce_appends`` the (still fully validated, cumulative
        capacity included) batch is queued instead and returns 0; the next
        ``flush()`` — or an explicit :meth:`apply_appends` — programs all
        queued batches as ONE delta per touched page, so N small appends
        between flushes cost the page programs of one combined append.
        """
        if self._pending:
            raise RuntimeError(
                f"append() with {len(self._pending)} queries pending; "
                "flush() first so no ticket spans the mutation"
            )
        try:
            return self._admit_append(rows)
        except ValueError as err:
            if not (self.grow_on_overflow and "overflows" in str(err)):
                raise
            # capacity growth rides the compaction rebuild: re-stripe into
            # wider pages (the failed attempt validated before mutating, so
            # nothing is half-applied), leaving the batch plus the original
            # headroom — or twice the batch, whichever is larger — free
            b = len(next(iter(rows.values())))
            self.compact(
                reserve_rows=b
                + max(2 * b, self.store.capacity_rows - self.store.live_rows)
            )
            return self._admit_append(rows)

    def _admit_append(self, rows: dict) -> int:
        if self.coalesce_appends:
            queue_append(self.store, self._append_buf, rows)
            return 0
        return self._program_append(rows)

    def _program_append(self, rows: dict) -> int:
        delta = self.store.append(rows)  # validates before mutating
        programs, words = self.store.program_delta(
            self.device, delta, telemetry=self.telemetry
        )
        self.telemetry.count("rows_appended", delta.rows)
        self.telemetry.count("esp_delta_programs", programs)
        self._count_programmed_words(delta, physical=words, logical=True)
        return programs

    def _count_programmed_words(
        self, delta, *, physical: int, logical: bool
    ) -> None:
        """Write-amplification accounting for one programmed delta.

        ``words_programmed`` counts the words physically ESP-programmed —
        ``physical`` comes from :meth:`BitmapStore.program_delta`, which
        under multi-level packing merges co-resident logical pages into one
        physical program (this is where the MLC density win shows up).
        ``words_written`` counts the words a client mutation had to change
        (``logical=True``) — always the per-logical-page sum, independent
        of the packing factor.  Compaction reprograms surviving data the
        client never touched, so it adds to the physical side only — the
        ratio is the index's write amplification
        (``stats()["write_amplification"]``, also in snapshots).
        """
        self.telemetry.count("words_programmed", physical)
        if logical:
            words = sum(int(pd.words.shape[0]) for pd in delta.pages)
            self.telemetry.count("words_written", words)

    @property
    def appends_queued(self) -> int:
        return len(self._append_buf)

    def apply_appends(self) -> int:
        """Program every queued append batch as one coalesced delta.

        A page touched by many queued batches programs ONCE (its combined
        tail words); returns the pages programmed.  Ran automatically at
        the top of ``flush()``, so queries submitted after an append always
        see its rows — identical semantics to immediate appends, minus the
        per-batch page programs.
        """
        if not self._append_buf:
            return 0
        rows = merge_appends(self._append_buf)
        self.telemetry.count(
            "append_batches_coalesced", len(self._append_buf)
        )
        self._append_buf.clear()
        return self._program_append(rows)

    # -- deletes / updates / compaction --------------------------------------
    def delete(self, row_ids) -> int:
        """Tombstone rows; returns pages ESP-programmed (always 1).

        Queued appends apply first so ``row_ids`` address the fully
        up-to-date table; like appends, deletes are refused while tickets
        are in flight.  The whole batch costs one delta-page program of
        the stripe's tombstone page — no other page changes, no region
        epoch moves, every cached plan stays warm (its spliced valid
        wordline reads the new tombstones on the next sensing).  May
        trigger the auto-compaction policy (``compact_density``).
        """
        if self._pending:
            raise RuntimeError(
                f"delete() with {len(self._pending)} queries pending; "
                "flush() first so no ticket spans the mutation"
            )
        self.apply_appends()
        delta = self.store.delete(row_ids)
        programs, words = self.store.program_delta(
            self.device, delta, telemetry=self.telemetry
        )
        self.telemetry.count("rows_deleted", len(np.asarray(row_ids)))
        self.telemetry.count("esp_delta_programs", programs)
        self._count_programmed_words(delta, physical=words, logical=True)
        self.telemetry.gauge(
            "tombstone_density", self.store.tombstone_density
        )
        self._maybe_compact()
        return programs

    def update(self, row_ids, rows: dict[str, object]) -> int:
        """Update = delete + append: tombstone ``row_ids``, append ``rows``
        (which get fresh row ids at the tail); returns pages programmed.

        Both halves validate BEFORE either mutates, so a bad update can
        never leave the rows deleted but not re-appended.  Reuses delta-
        page programming + region epochs end to end: a value-stable update
        (no first-seen value, no grown BSI width) invalidates no plan.
        """
        if self._pending:
            raise RuntimeError(
                f"update() with {len(self._pending)} queries pending; "
                "flush() first so no ticket spans the mutation"
            )
        self.apply_appends()
        ids = self.store.check_delete(row_ids)
        arrays = {c: np.asarray(v) for c, v in rows.items()}
        b = self.store.check_append(arrays)
        if b != ids.size:
            raise ValueError(
                f"update() got {ids.size} row ids but {b} replacement rows"
            )
        n = self.delete(ids)
        n += self.append(arrays)
        self.telemetry.count("rows_updated", ids.size)
        return n

    def _maybe_compact(self) -> bool:
        """The background-compaction policy: rebuild once tombstone density
        crosses ``compact_density`` (checked only at mutation boundaries,
        with no tickets in flight by construction)."""
        if (
            self.compact_density is None
            or self.store.tombstone_density < self.compact_density
        ):
            return False
        self.compact()
        return True

    def compact(self, reserve_rows: int | None = None) -> dict:
        """Rewrite the stripe without its tombstoned rows; returns stats.

        The erase-unit-aware rebuild a real device must do: NAND programs
        only 1->0, so reclaiming tombstoned capacity means erasing every
        block the stripe occupies (charged per block at ``t_bers_ms`` in
        the SSD projection, one P/E cycle each) and ESP-reprogramming the
        surviving rows.  Surviving rows are renumbered densely (row ``k``
        = the k-th live row in old id order); ``reserve_rows`` sets the
        fresh append headroom and defaults to restoring the stripe's full
        pre-compaction capacity — this same path grows capacity when
        ``grow_on_overflow`` re-stripes into wider pages.  The reprogram
        counts toward physical (but not logical) programmed words: the
        write-amplification cost of garbage collection.
        """
        if self._pending:
            raise RuntimeError(
                f"compact() with {len(self._pending)} queries pending; "
                "flush() first so no ticket spans the rebuild"
            )
        self.apply_appends()
        store, tele = self.store, self.telemetry
        t0 = time.perf_counter()
        dropped = store.deleted_rows
        live = store.live_bits()
        table = {c: v[live] for c, v in store.to_table().items()}
        if reserve_rows is None:
            reserve_rows = store.capacity_rows - store.live_rows
        schema = {c: ci.values for c, ci in store.columns.items()}
        erased = self.device.erase_rebuild()
        store.rebuild(table, reserve_rows=reserve_rows, schema=schema)
        _, words = store.program(self.device)
        self.device.reset_after_rebuild()
        self._flush_programs.clear()
        self._extras_cache.clear()
        self._cse_cache.clear()
        self._mask_cache = None
        tele.count("compactions")
        tele.count("block_erases", erased)
        tele.count("words_programmed", words)
        tele.count("compaction_rows_dropped", dropped)
        tele.gauge("tombstone_density", 0.0)
        self._record_wear()
        t1 = time.perf_counter()
        tele.span("compact", "flush", t0, t1, args={"erased": erased})
        tele.observe("compact_s", t1 - t0)
        return {
            "rows_dropped": dropped,
            "live_rows": store.num_rows,
            "capacity_rows": store.capacity_rows,
            "blocks_erased": erased,
            "words_reprogrammed": words,
            "seconds": t1 - t0,
        }

    def _record_wear(self) -> None:
        """Per-block wear gauges (P/E cycles) after erase-heavy operations."""
        pec = self.device.pec
        if pec:
            self.telemetry.gauge("max_pec", max(pec.values()))
            self.telemetry.gauge(
                "mean_pec", sum(pec.values()) / len(pec)
            )

    # -- admission ----------------------------------------------------------
    def submit(self, query: Query) -> int:
        """Admit a query; returns its ticket.  Queries execute on the next
        ``flush()`` (or ``serve()``), ``max_batch`` at a time.

        Validation (predicate columns + the aggregate's target columns)
        happens here, so a bad query raises immediately instead of
        poisoning a later flush.
        """
        validate_query(query, self.store.columns)
        ticket = self._next_ticket
        self._next_ticket += 1
        self._pending.append((ticket, query, time.perf_counter()))
        return ticket

    @property
    def pending(self) -> int:
        return len(self._pending)

    # -- serving -------------------------------------------------------------
    def flush(self) -> dict[int, QueryResult]:
        """Compile, batch-execute, and aggregate all pending queries.

        The flush runs as four back-to-back stages (``compile``,
        ``dispatch``, ``transfer``, ``reduce``; the legacy path records
        ``reduce+transfer`` in place of ``transfer``), with ``build`` and
        ``launch`` inside ``dispatch`` and ``wait`` and ``copy`` inside
        ``transfer`` on the fused path; every stage span carries the flush
        id (see :meth:`Telemetry.stage`)."""
        self.apply_appends()
        if not self._pending:
            return {}
        self._materialize_hot()
        tele = self.telemetry
        batch, self._pending = (
            self._pending[: self.max_batch],
            self._pending[self.max_batch :],
        )
        fid = int(self.flushes) + 1
        with tele.stage("compile", flush=fid) as st:
            compiled = [self.compiler.compile(q) for _, q, _ in batch]
            execs = [self.compiler.exec_for(cq) for cq in compiled]
        t0, t_comp = st.start, st.end
        cse = None
        fused = self.fuse_flush and not self.device._non_esp
        with tele.stage("dispatch", t_comp, flush=fid) as st:
            if (
                self._mask_cache is None
                or self._mask_cache[0] != self.store.epoch
            ):
                valid = self.store.valid_words_mask()
                self._mask_cache = (self.store.epoch, jnp.asarray(valid))
                tele.count("h2d_bytes", valid.nbytes)
            mask_words = self._mask_cache[1]
            queries = [q for _, q, _ in batch]
            aggs = [get_aggregator(q.agg) for q in queries]
            if fused:
                # the fused path: ONE jitted program senses every signature
                # group and reduces every aggregate kind device-side; ONE
                # payload transfer brings back the whole flush.
                with tele.stage("build", flush=fid):
                    cse, program = self._flush_program(
                        compiled, execs, queries, aggs
                    )
                self.last_program = program
                store = self.device.store
                uploads = store.snapshot_uploads
                data = store.snapshot()
                if store.snapshot_uploads != uploads:
                    tele.count("h2d_bytes", data.nbytes)
                with tele.stage("launch", flush=fid):
                    payload = program.run(data, mask_words)
                placed, gathered = program.sensings
                tele.count("mws_sensed_in_place", placed)
                tele.count("mws_sensed_gathered", gathered)
                if cse is None:
                    age_spill_blocks(self.device.pec, execs)
                else:
                    # wear: one run of each UNIQUE member plan + each
                    # shared plan, plus one scratch program per shared
                    # result
                    age_spill_blocks(
                        self.device.pec,
                        [cse.member_execs[i] for i in cse.uix]
                        + list(cse.shared_execs),
                    )
                    for b in cse.shared_blocks:
                        self.device.pec[b] = self.device.pec.get(b, 0) + 1
                tele.count("fused_dispatches")
                self.device.last_signature_groups = program.n_sense_groups
            else:
                # legacy path (devices with non-ESP pages, and the oracle
                # for the differential harness): vmap batches + one reduce
                # dispatch and one transfer per reduce signature
                plans = [c.plan for c in compiled]
                stacked = (
                    self.device.execute_batch_stacked(
                        plans,
                        execs=execs,
                        # epochs inside cq.key make the memoized grouping
                        # impossible to hit stale
                        batch_key=tuple(cq.key for cq in compiled),
                    )
                    & mask_words
                )  # (B, W), padding zeroed
        t_disp = st.end
        if fused:
            # the single device->host copy of the flush (also the barrier
            # that keeps qps/latency from measuring only Python dispatch)
            with tele.stage("transfer", t_disp, flush=fid) as st:
                with tele.stage("wait", flush=fid):
                    payload.block_until_ready()
                with tele.stage("copy", flush=fid):
                    host = jax.device_get(payload)
                tele.count("host_transfers")
            t_xfer = st.end
            with tele.stage("reduce", t_xfer, flush=fid) as st:
                partials = program.unpack(host, aggs)
                extra_counts = list(program.extra_counts)
        else:
            partials, extra_counts, n_groups = reduce_flush(
                stacked,
                [q.agg for q in queries],
                [self.store] * len(queries),
                [self.store.epoch] * len(queries),
                interpret=self.device.interpret,
                extras_cache=self._extras_cache,
            )
            tele.count("host_transfers", n_groups)
            tele.count("eager_plans", self.device.last_eager_plans)
            t_xfer = time.perf_counter()
            # force device work before timestamping, or qps/latency would
            # only measure the Python-side dispatch
            with tele.stage("reduce", t_xfer, flush=fid) as st:
                jax.block_until_ready(stacked)
            tele.span("reduce+transfer", "flush", t_disp, t_xfer)
        t1 = st.end
        if cse is not None:
            # physical traffic after CSE: each UNIQUE member plan runs once
            # (duplicates ride the member gather) plus each shared subplan
            wls = thr = 0
            for p in list(cse.member_plans) + list(cse.shared_plans):
                wls += record_plan_traffic(self.command_shape_counts, p)
                thr += plan_thresholds(p)
            tele.count("wordlines_sensed", wls)
            if thr:
                tele.count("threshold_senses", thr)
            tele.count("cse_plan_hits", cse.n_dedup_hits)
            tele.count("cse_shared_senses", len(cse.shared_plans))
            tele.count("cse_rewritten_members", cse.n_rewritten)
            tele.count("cse_spill_programs", len(cse.shared_plans))
        results: dict[int, QueryResult] = {}
        queue_wait = 0.0
        for i, ((ticket, q, t_submit), cq) in enumerate(zip(batch, compiled)):
            agg = aggs[i]
            queue_wait += t0 - t_submit
            self._host_postprocess |= agg.host_postprocess
            if cse is None:
                self.telemetry.count(
                    "wordlines_sensed",
                    record_plan_traffic(self.command_shape_counts, cq.plan),
                )
                thr = plan_thresholds(cq.plan)
                if thr:
                    self.telemetry.count("threshold_senses", thr)
            # each extra plane the aggregate sensed (a BSI slice or an
            # equality bitmap) is one single-wordline read in the
            # projected traffic
            if extra_counts[i]:
                self.command_shape_counts[AGG_READ_SHAPE] += extra_counts[i]
                tele.count("wordlines_sensed", extra_counts[i])
            attr = None
            if tele.enabled:
                attr = {
                    "sensings": plan_sensings(cq.plan) + extra_counts[i],
                    "wordlines": plan_traffic(cq.plan)[1] + extra_counts[i],
                    "spill_steps": execs[i].spills if execs[i] else 0,
                    "agg_plane_reads": extra_counts[i],
                    "queue_s": t0 - t_submit,
                    "compile_s": t_comp - t0,
                    "device_s": t_disp - t_comp,
                    "transfer_s": t_xfer - t_disp,
                    "reduce_s": t1 - t_xfer,
                }
                tele.observe("queue_wait_s", t0 - t_submit)
                attribute_result(tele, ticket, q, attr, t_submit, t1, fid)
            results[ticket] = QueryResult(
                ticket,
                q,
                agg.finalize(partials[i], self.store),
                t1 - t_submit,
                cq.cache_hit,
                attribution=attr,
            )
            tele.count("total_latency_s", t1 - t_submit)

        tele.count("queries_served", len(batch))
        tele.count("queue_wait_s", queue_wait)
        tele.count("flushes")
        tele.count("vmap_batches", self.device.last_signature_groups)
        tele.count("serve_time_s", t1 - t0)
        tele.span(
            "flush",
            "flush",
            t0,
            t1,
            args={"flush": fid, "batch": len(batch)},
        )
        tele.observe("flush_latency_s", t1 - t0)
        return results

    def _flush_program(self, compiled, execs, queries, aggs):
        """The flush's CSE rewrite and fused program, each looked up or
        built; returns ``(cse, program)`` (``cse`` is None with the
        optimizer off).

        Epochs inside the plan keys + the content epochs make stale hits
        impossible.  Plan keys cover only the predicate side, so the
        members' aggregate specs join the key explicitly — the same
        predicates under different aggregates are different programs.
        """
        cse = None
        if self.optimize:
            ckey = (
                tuple(cq.key for cq in compiled),
                self.store.epoch,
                self.device.store.epoch,
            )
            cse = self._cse_cache.get(ckey)
            if cse is None:
                if len(self._cse_cache) >= 64:
                    self._cse_cache.clear()
                cse = cse_flush(compiled, self.compiler, self.device)
                self._cse_cache[ckey] = cse
        key = (
            tuple(cq.key for cq in compiled),
            tuple(a.spec for a in aggs),
            self.store.epoch,
            self.device.store.epoch,
        )
        program = self._flush_programs.get(key)
        if program is None:
            if len(self._flush_programs) >= 64:
                self._flush_programs.clear()
            program = compile_flush(
                execs if cse is None else list(cse.member_execs),
                [q.agg for q in queries],
                [self.store] * len(queries),
                [self.store.epoch] * len(queries),
                words=self.store.words,
                interpret=self.device.interpret,
                runner_cache=self._runner_cache,
                extras_cache=self._extras_cache,
                pad=self.device.pad_signatures,
                dedup_keys=None if cse is None else list(cse.dedup_keys),
                shared_execs=() if cse is None else cse.shared_execs,
            )
            self._flush_programs[key] = program
            self.telemetry.count("h2d_bytes", program.upload_bytes)
        return cse, program

    def serve(self, queries: list[Query]) -> list[QueryResult]:
        """Submit + flush until drained; results in submission order."""
        tickets = [self.submit(q) for q in queries]
        results: dict[int, QueryResult] = {}
        while self._pending:
            results.update(self.flush())
        return [results[t] for t in tickets]

    # -- reporting -----------------------------------------------------------
    def stats(self) -> dict:
        served = max(self.queries_served, 1)
        return {
            "queries_served": self.queries_served,
            "flushes": self.flushes,
            "vmap_batches": self.vmap_batches,
            "eager_plans": self.eager_plans,
            "plan_cache_hits": self.compiler.hits,
            "plan_cache_misses": self.compiler.misses,
            "plan_cache_size": self.compiler.cache_size,
            "queries_per_sec": (
                self.queries_served / self.serve_time_s
                if self.serve_time_s
                else float("inf")
            ),
            "mean_latency_s": self.total_latency_s / served,
            "mws_commands": sum(self.command_shape_counts.values()),
            "sensings_per_query": (
                sum(self.command_shape_counts.values()) / served
            ),
            "threshold_senses": self.threshold_senses,
            "cse_plan_hits": self.cse_plan_hits,
            "cse_shared_senses": self.cse_shared_senses,
            "materializations": self.materializations,
            "materialization_hits": self.materialization_hits,
            "fused_dispatches": self.fused_dispatches,
            "host_transfers": self.host_transfers,
            "rows_appended": self.rows_appended,
            "esp_delta_programs": self.esp_delta_programs,
            "append_batches_coalesced": self.append_batches_coalesced,
            "rows_deleted": self.rows_deleted,
            "rows_updated": self.rows_updated,
            "compactions": self.compactions,
            "block_erases": self.block_erases,
            "live_rows": self.store.live_rows,
            "tombstone_density": self.store.tombstone_density,
            "write_amplification": (
                self.words_programmed / self.words_written
                if self.words_written
                else 1.0
            ),
        }

    def projection(self, ssd: SSDConfig = DEFAULT_SSD) -> dict:
        """Full-scale SSD time/energy projection of the served traffic.

        Replays every executed MWS command shape through the paper's timing
        and energy model at Table-1 geometry, with the result bitmaps of all
        served queries streamed out — reported next to the outside-storage
        (OSP) baseline that would sense and ship every operand page.
        """
        return project_traffic(
            self.command_shape_counts,
            wordlines_sensed=int(self.wordlines_sensed),
            num_rows=self.store.num_rows,
            num_queries=int(self.queries_served),
            host_postprocess=self._host_postprocess,
            # appends' delta programs + CSE scratch-page programs + hot-
            # predicate materialization programs all ride the ESP path
            esp_programs=int(
                self.esp_delta_programs
                + self.cse_spill_programs
                + self.materialization_programs
            ),
            block_erases=int(self.block_erases),
            levels=self.device.layout.levels,
            ssd=ssd,
            name=f"flashql({int(self.queries_served)}q)",
        )


registry_counters(
    BatchScheduler,
    (
        "queries_served",
        "flushes",
        "vmap_batches",
        "eager_plans",
        "serve_time_s",
        "total_latency_s",
        "fused_dispatches",
        "host_transfers",
        "rows_appended",
        "esp_delta_programs",
        "append_batches_coalesced",
        "wordlines_sensed",
        "threshold_senses",
        "rows_deleted",
        "rows_updated",
        "compactions",
        "block_erases",
        "words_programmed",
        "words_written",
        "compaction_rows_dropped",
        "cse_plan_hits",
        "cse_shared_senses",
        "cse_rewritten_members",
        "cse_spill_programs",
        "materializations",
        "materialization_hits",
        "materialization_invalidations",
        "materialization_programs",
    ),
)
