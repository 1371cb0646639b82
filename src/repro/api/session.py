"""GraphSession: one graph, many standing queries, one commit per epoch.

The facade over the paper's engines (ROADMAP north-star shape, cf. HUGE
arXiv:2103.14294 / DDSL arXiv:1810.05972): a session OWNS the dynamic graph
— one :class:`~repro.core.delta.RegionStore` holding every multi-version
index projection (host-local, or hash-sharded over a device mesh) — and is
the sole public entry point.  Queries register against the session and get a
:class:`QueryHandle` (static count/enumerate + standing delta subscription);
``session.update`` runs ONE normalize → dAQ_1..dAQ_n (for every registered
query) → commit per epoch off the shared regions, so N standing queries pay
neither N index copies nor N commits.

Compiled artifacts are cached at every layer: plans per (query, mode),
single-host dataflows per (plan, config) (``bigjoin._compiled_fns``), and
mesh programs per (plan, config, mesh)
(``distributed.get_distributed_program``) — steady-state epochs recompile
nothing.

Capacities (B' proposal budget, output buffers, route slots) are sized
automatically from the query's AGM bound; pass overrides only when you know
better.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core import compilestats
from repro.core import delta as _delta
from repro.core.bigjoin import BigJoinConfig, run_bigjoin
from repro.core.csr import pow2_capacity, unique_rows
from repro.core.plan import Plan, make_plan
from repro.core.query import Query, fractional_edge_cover, query_by_name
from repro.api.dsl import parse_pattern
from repro.errors import (CapacityOverflow, ESCALATES_BATCH, ESCALATES_OUT,
                          ESCALATES_ROUTE)


def _pow2(n: int) -> int:
    return pow2_capacity(max(int(n), 1))


@dataclasses.dataclass(frozen=True)
class Sizing:
    """Derived capacities for one query (see :func:`auto_sizing`)."""

    batch: int  # B' — per-step proposal budget
    out_capacity: int  # collect-mode output rows per dataflow run
    route_capacity: int  # per peer-pair request slots (mesh only)


def auto_sizing(query: Query, num_edges: int, num_workers: int = 1,
                update_batch: int = 2048) -> Sizing:
    """Capacity defaults from the AGM bound (§1.1): with |E| = IN and
    fractional edge-cover number rho*, MaxOut = IN^rho* and one seed edge
    extends to at most IN^(rho*-1) results.

    - ``batch`` (B', PER WORKER): enough proposals per step to amortize a
      launch but bounded for VMEM — the per-seed extension bound, clamped
      to [1024, 8192] globally and split across workers no lower than 256.
    - ``out_capacity``: one epoch's worst-case signed output,
      n_atoms · |dR| · IN^(rho*-1), clamped to [2^14, 2^22].
    - ``route_capacity``: the BiGJoin-S balls-into-bins regime — each
      worker's B' per-step requests spread over w owners, 4x slack:
      4·batch/w per peer pair, floor 64 (matches
      ``distributed.default_delta_config``).
    """
    E = max(int(num_edges), 2)
    rho = fractional_edge_cover(query)
    per_seed = float(E) ** max(rho - 1.0, 0.0)
    batch = int(np.clip(_pow2(per_seed), 1024, 8192))
    batch = max(batch // max(num_workers, 1), 256)
    out_rows = query.num_atoms * update_batch * per_seed
    out_capacity = int(np.clip(_pow2(out_rows), 1 << 14, 1 << 22))
    return Sizing(batch, out_capacity, _route_for(batch, num_workers))


def _route_for(batch: int, num_workers: int) -> int:
    return max(4 * batch // max(num_workers, 1), 64)


@dataclasses.dataclass
class EpochResult:
    """What one ``session.update`` produced: the normalized batch and each
    registered query's signed output delta (keyed by handle name).

    ``ins`` / ``dels`` are the EDGE relation's normalized rows (empty when
    the epoch touched other relations only); ``by_rel`` carries every
    relation's normalized ``(ins, dels)`` pair.  ``compile_events`` counts
    the jit traces (= XLA compiles) this epoch triggered — after
    :meth:`GraphSession.prewarm` it must be ZERO on every warm epoch
    (DESIGN.md §8), which is what the compile-stability suite asserts.
    """

    epoch: int
    ins: np.ndarray
    dels: np.ndarray
    deltas: Dict[str, _delta.DeltaResult]
    by_rel: Dict[str, Tuple[np.ndarray, np.ndarray]] = \
        dataclasses.field(default_factory=dict)
    compile_events: int = 0

    @property
    def is_noop(self) -> bool:
        return all(i.size == 0 and d.size == 0
                   for i, d in self.by_rel.values()) \
            if self.by_rel else (self.ins.size == 0 and self.dels.size == 0)

    def advance(self, live: np.ndarray) -> np.ndarray:
        """Advance a host live-edge array by this epoch's normalized delta
        (np.unique row order, same as ``session.edges``) — lets stream
        drivers track the live set without pulling the device-resident
        store's O(|E|) mirror every epoch."""
        if self.is_noop:
            return live
        kept = _delta._diff_rows(live, self.dels)
        return unique_rows(np.concatenate([kept, self.ins]))


class QueryHandle:
    """One standing query registered on a :class:`GraphSession`.

    Static evaluation (:meth:`count` / :meth:`enumerate`) reads the live
    graph through the session's shared regions; the standing side is fed by
    ``session.update`` — every epoch's :class:`~repro.core.delta.DeltaResult`
    lands in :attr:`last_delta`, accumulates into :attr:`net_change`, and is
    pushed to any :meth:`subscribe` callbacks.
    """

    def __init__(self, session: "GraphSession", name: str, query: Query,
                 batch: Optional[int] = None,
                 out_capacity: Optional[int] = None):
        self.session = session
        self.name = name
        self.query = query
        self._batch = batch
        self._out_capacity = out_capacity
        self._engine: Optional[_delta.DeltaBigJoin] = None
        self.last_delta: Optional[_delta.DeltaResult] = None
        self.net_change = 0
        self._subscribers: List[Callable] = []

    @property
    def engine(self) -> _delta.DeltaBigJoin:
        """The standing delta engine (shares the session's RegionStore).
        Built lazily on the first update epoch, so static-only handles
        never pay the delta plans' region construction."""
        if self._engine is None:
            self._engine = self.session._make_engine(
                self.query, self._batch, self._out_capacity)
        return self._engine

    def count(self) -> int:
        """Exact instance count over the CURRENT graph (worst-case optimal
        static dataflow over the shared live regions)."""
        return self.session._static_eval(self.query, "count").count

    def enumerate(self) -> Tuple[np.ndarray, np.ndarray]:
        """All instances over the current graph: (tuples [N, m], weights)."""
        res = self.session._static_eval(self.query, "collect")
        m = self.query.num_attrs
        if res.tuples is None:
            return (np.zeros((0, m), np.int32), np.zeros(0, np.int32))
        return res.tuples, res.weights

    def subscribe(self, fn: Callable[[int, _delta.DeltaResult], None]):
        """Call ``fn(epoch, delta_result)`` after every update epoch."""
        self._subscribers.append(fn)
        return fn

    def _deliver(self, epoch: int, res: _delta.DeltaResult):
        self.last_delta = res
        self.net_change += res.count_delta
        for fn in self._subscribers:
            fn(epoch, res)

    def __repr__(self):  # pragma: no cover - debug aid
        return (f"QueryHandle({self.name!r}, atoms={self.query.num_atoms}, "
                f"net_change={self.net_change:+d})")


class GraphSession:
    """The facade: owns one dynamic graph and serves many standing queries.

    Engine selection: ``local=True`` keeps everything on the host
    (single-process BiGJoin); ``local=False`` hash-shards every index region
    over the device mesh and runs the request/response dataflow of §3.4.
    Default (``local=None``): the mesh when more than one device (or an
    explicit ``mesh``) is available, the host engine otherwise.

    Either way the session's RegionStore is DEVICE-RESIDENT by default
    (DESIGN.md §6): one jitted normalize probe and one jitted sorted-merge
    commit per epoch serve every registered query, with warm epoch cost
    proportional to the delta, not the graph.  ``device_resident=False``
    selects the legacy host-truth store (contrast benchmarks only).
    """

    def __init__(self, initial_edges, *, local: bool = None,
                 mesh=None, balance: bool = False,
                 batch: Optional[int] = None,
                 out_capacity: Optional[int] = None,
                 update_batch: int = 2048,
                 compact_ratio: float = 0.5,
                 device_resident: bool = True,
                 prewarm: bool = False):
        import jax
        if local is None:
            local = mesh is None and jax.device_count() == 1
        self.local = bool(local)
        self.balance = balance
        self._batch_override = batch
        self._out_override = out_capacity
        self.update_batch = update_batch
        if self.local:
            self.mesh = None
            self.w = 1
        else:
            if mesh is None:
                from jax.sharding import Mesh
                from repro.core.distributed import AXIS
                mesh = Mesh(np.array(jax.devices()), (AXIS,))
            self.mesh = mesh
            self.w = int(np.prod(
                [mesh.shape[a] for a in mesh.axis_names]))
        self.store = _delta.RegionStore(
            initial_edges, shard_w=0 if self.local else self.w,
            mesh=self.mesh, compact_ratio=compact_ratio,
            device_resident=device_resident)
        self.handles: Dict[str, QueryHandle] = {}
        self.epoch = 0
        self._static_plans: Dict[Query, Plan] = {}
        self.programs_built = 0  # engine/program constructions (cache proof)
        # walk the AOT compile ladder at register() time (DESIGN.md §8)
        self.auto_prewarm = bool(prewarm)

    # -- registration -------------------------------------------------------
    def register(self, pattern, name: Optional[str] = None,
                 symmetric: bool = False,
                 batch: Optional[int] = None,
                 out_capacity: Optional[int] = None) -> QueryHandle:
        """Register a standing query and return its handle.

        ``pattern`` is a :class:`Query`, a DSL string (``"tri(a,b,c) :=
        e(a,b), e(a,c), e(b,c)"``), or a registry name (``"4-clique"``).
        Registering the same name twice returns the existing handle.
        """
        if isinstance(pattern, Query):
            q = pattern
        elif ":=" in pattern:
            q = parse_pattern(pattern, name=name)
        else:
            q = query_by_name(pattern, symmetric=symmetric)
        name = name or q.name
        if name in self.handles:
            if self.handles[name].query != q:
                raise ValueError(
                    f"query name {name!r} already registered with a "
                    "different pattern")
            return self.handles[name]
        # declare any relation the query reads that the store doesn't hold
        # yet (created empty; add_relation() beforehand seeds real tuples)
        # — so ``update({"tri": ...})`` works right after registration,
        # without waiting for the lazily-built engine to declare it
        for atom in q.atoms:
            if atom.rel not in self.store.relations:
                self.store.add_relation(
                    atom.rel, np.zeros((0, atom.arity), np.int32))
        handle = QueryHandle(self, name, q, batch, out_capacity)
        self.handles[name] = handle
        if self.auto_prewarm:
            self.prewarm()
        return handle

    def prewarm(self, horizon: Optional[int] = None) -> int:
        """Walk the AOT compile ladder (DESIGN.md §8): pin the delta/probe/
        seed marks to ``update_batch``, then compile-and-execute (on
        zero-filled prototypes — see ``delta._warm_call``) every fold and
        dataflow signature the ratcheted capacity ladder can request for
        every registered query — store folds
        (``RegionStore.prewarm_folds``), the local step/seed_step pairs,
        and the mesh shard_map programs.  ``horizon`` optionally caps the
        warmed committed ladder at the stream's total expected churn
        (epochs × batch) so short streams over huge graphs don't pay for
        rungs they can never reach.

        After this, every epoch with batches ≤ ``update_batch`` reports
        ``EpochResult.compile_events == 0`` until a relation's base region
        outgrows its pow2 rung (amortized-rare; that one epoch re-walks a
        warm-cached ladder).  With the persistent compilation cache
        (on by default, see ``compilestats``) a restarted process pays deserialization,
        not XLA, for the same ladder.  Returns compile events spent (also
        surfaced as ``StoreStats.prewarm_compiles``)."""
        snap = compilestats.snapshot()
        # engines first: their lazily-created projections must exist
        # before the store enumerates fold groups
        engines = [h.engine for h in self.handles.values()]
        self.store.prewarm_folds(self.update_batch, horizon)
        for engine in engines:
            self.store.stats.prewarm_compiles += \
                engine.prewarm(self.update_batch, horizon)
        self.store._sync_compile_stats()
        return compilestats.since(snap)

    def kernel_coverage(self) -> dict:
        """Per-relation Pallas-dispatch evidence (``RegionStore.
        kernel_coverage``): for each relation, the traced ``pallas_call``
        count of the exact commit fold and probe the warm serving path
        dispatches to.  The CI kernel-coverage gate asserts zero warm
        compiles AND a fused (single-launch) fold on every composite
        relation from this one dict."""
        return self.store.kernel_coverage(self.update_batch)

    def query_by_name(self, name: str) -> QueryHandle:
        """Fetch a registered handle; registers the named motif on miss."""
        return self.handles.get(name) or self.register(name)

    def __getitem__(self, name: str) -> QueryHandle:
        return self.handles[name]

    def add_relation(self, rel: str, rows: np.ndarray,
                     arity: Optional[int] = None):
        """Register one more dynamic relation (e.g. a materialized ``tri``
        relation) with its initial tuples; later ``update`` batches may
        then address it by name."""
        self.store.add_relation(rel, rows, arity=arity)

    def relation(self, rel: str) -> np.ndarray:
        """One relation's live tuples (host view)."""
        return self.store.relation_rows(rel)

    def num_tuples(self, rel: str) -> int:
        return self.store.num_tuples(rel)

    def _sizing(self, q: Query, batch, out_capacity) -> Sizing:
        # the AGM inputs ride a ratchet: |E| jitter around a pow2 boundary
        # must not flap the derived B'/out/route capacities (each one keys
        # a jit cache — DESIGN.md §8)
        live = self.store.base_ratchet.capacity(
            ("sizing",), self.store.max_live or self.update_batch)
        s = auto_sizing(q, live, self.w, self.update_batch)
        b = batch or self._batch_override or s.batch
        oc = out_capacity or self._out_override or s.out_capacity
        # escalation marks (DESIGN.md §10) are FLOORS: once an overflow
        # escalated a query's rung, every rebuilt engine / static-eval
        # config / restored session starts at the raised capacity instead
        # of re-discovering the overflow
        r = self.store.ratchet
        b = max(b, r.peek(("cap", "batch", q.name)))
        oc = max(oc, r.peek(("cap", "out", q.name)))
        rt = max(_route_for(b, self.w),  # route follows the FINAL B'
                 r.peek(("cap", "route", q.name)))
        return Sizing(b, oc, rt)

    def _make_engine(self, q: Query, batch, out_capacity
                     ) -> _delta.DeltaBigJoin:
        s = self._sizing(q, batch, out_capacity)
        self.programs_built += 1
        if self.local:
            cfg = BigJoinConfig(batch=s.batch, seed_chunk=s.batch,
                                mode="collect", out_capacity=s.out_capacity)
            return _delta.DeltaBigJoin(q, None, cfg=cfg, store=self.store)
        from repro.core.distributed import (DistDeltaBigJoin,
                                            default_delta_config)
        dcfg = default_delta_config(self.w, batch=s.batch,
                                    out_capacity=s.out_capacity,
                                    balance=self.balance)
        return DistDeltaBigJoin(q, None, mesh=self.mesh, dcfg=dcfg,
                                store=self.store)

    # -- the epoch loop -----------------------------------------------------
    def prepare(self, updates, weights=None) -> _delta.PreparedBatch:
        """Stage A of :meth:`update` on the host only (validate, pack,
        sentinel-pad — pure numpy, no device call): the serving pipeline
        prepares batch k+1 on a prep thread while batch k is still
        committing, then passes the result to ``update(prepared=...)``
        (DESIGN.md §9)."""
        return self.store.prepare(updates, weights)

    def update(self, updates=None, weights=None, *,
               prepared: Optional[_delta.PreparedBatch] = None
               ) -> EpochResult:
        """Apply one update batch to the graph and every standing query:
        ONE normalize, one staged uncommitted region set, each registered
        query's dAQ pipeline off the shared regions, ONE commit.

        ``updates`` is an [N, 2] edge array (with optional ``weights``), or
        a per-relation dict ``{"edge": (rows, w), "tri": (rows, w), ...}``
        updating any subset of the session's relations in one epoch —
        or pass ``prepared=`` (from :meth:`prepare`) to skip the host
        packing stage.

        TRANSACTIONAL (DESIGN.md §10): the epoch counter advances and the
        handles observe the delta only after the commit succeeded.  Any
        failure between staging and commit — a capacity overflow that
        exhausted its escalations, an injected fault — rolls the store
        back to the epoch boundary and re-raises; the same batch can then
        be retried verbatim.
        """
        snap = compilestats.snapshot()
        if prepared is None:
            prepared = self.store.prepare(updates, weights)
        elif updates is not None or weights is not None:
            raise ValueError("pass updates OR prepared=, not both")
        batches = self.store.normalize_prepared(prepared)
        e_ins, e_dels = batches.get(
            "edge", (np.zeros((0, 2), np.int32),) * 2)
        if all(i.size == 0 and d.size == 0 for i, d in batches.values()):
            self.epoch += 1
            zero = _delta.DeltaResult(0, None, None, [])
            deltas = {name: zero for name in self.handles}
            for name, h in self.handles.items():
                h._deliver(self.epoch, zero)
            return EpochResult(self.epoch, e_ins, e_dels, deltas, batches,
                               compile_events=compilestats.since(snap))
        # touch every handle's engine BEFORE staging: a lazily-built engine
        # must create its projections first, or they would miss the
        # uncommitted batch begin_epoch installs on existing regions
        engines = [(name, h.engine) for name, h in self.handles.items()]
        try:
            self.store.begin_epoch(batches)
            deltas: Dict[str, _delta.DeltaResult] = {}
            for name, engine in engines:
                deltas[name] = engine.run_delta_plans(batches)
            self.store.commit(batches)
        except Exception:
            self.store.rollback()
            raise
        self.epoch += 1
        for name, h in self.handles.items():
            h._deliver(self.epoch, deltas[name])
        return EpochResult(self.epoch, e_ins, e_dels, deltas, batches,
                           compile_events=compilestats.since(snap))

    # -- durability (DESIGN.md §9) ------------------------------------------
    def snapshot(self) -> Tuple[List[np.ndarray], dict]:
        """Serialize the session's dynamic state: the store's regions and
        ratchet marks (``RegionStore.snapshot``) plus the session layer —
        epoch counter and every registered handle (pattern DSL round-trip
        + accumulated ``net_change``).  Returns ``(leaves, meta)`` ready
        for ``repro.checkpoint.save_pytree(leaves, ..., extra=meta)``;
        restore with :meth:`restore` on a session of the same mesh
        width/engine mode."""
        from repro.api.dsl import pattern_of
        leaves, meta = self.store.snapshot()
        meta["session"] = {
            "epoch": int(self.epoch),
            "w": int(self.w),
            "local": bool(self.local),
            "update_batch": int(self.update_batch),
            "handles": {name: {"pattern": pattern_of(h.query),
                               "net_change": int(h.net_change)}
                        for name, h in self.handles.items()},
        }
        return leaves, meta

    def restore(self, leaves: List[np.ndarray], meta: dict) -> None:
        """Restore a :meth:`snapshot` onto this session in place: store
        regions + ratchet marks first, then the session layer (epoch,
        handles re-registered from their pattern DSL with net_change
        reinstated).  Handles already registered with the same name keep
        their handle object (and subscribers); the snapshot's counters
        overwrite theirs.  A WAL replay on top of this brings the session
        to the exact pre-crash state (``repro.serve.wal``)."""
        sess = meta.get("session", {})
        w = int(sess.get("w", self.w))
        if w != self.w:
            raise ValueError(
                f"snapshot was taken on a {w}-worker session; this one has "
                f"{self.w} workers — failover restores onto the same mesh "
                "width")
        if bool(sess.get("local", self.local)) != self.local:
            raise ValueError("snapshot engine mode (local/mesh) mismatch")
        self.store.restore(leaves, meta)
        self.epoch = int(sess.get("epoch", 0))
        for name, rec in sess.get("handles", {}).items():
            h = self.register(rec["pattern"], name=name)
            h.net_change = int(rec["net_change"])
            h.last_delta = None

    # -- static evaluation over the shared regions --------------------------
    def _static_plan(self, q: Query) -> Plan:
        """Plan reading version "old" = base + cins − cdel, i.e. the live
        committed graph, through the SAME shared regions the delta path
        maintains — a static query costs no extra index build."""
        plan = self._static_plans.get(q)
        if plan is None:
            plan = make_plan(q, versions=("old",) * q.num_atoms)
            self.store.ensure_plan(plan)
            self._static_plans[q] = plan
        return plan

    def _escalate_static(self, q: Query, exc: CapacityOverflow,
                         s: Sizing) -> None:
        """Static-eval overflow recovery: bump the same per-query marks
        the delta engines use (``_sizing`` applies them as floors, so the
        retried config — and every later engine build — starts on the
        raised rung).  Re-raises when no named buffer can grow."""
        r = self.store.ratchet
        changed = False
        if exc.kinds & ESCALATES_OUT:
            r.escalate(("cap", "out", q.name), floor=s.out_capacity)
            changed = True
        if exc.kinds & ESCALATES_BATCH:
            r.escalate(("cap", "batch", q.name), floor=s.batch)
            changed = True
        if exc.kinds & ESCALATES_ROUTE:
            r.escalate(("cap", "route", q.name), floor=s.route_capacity)
            changed = True
        if not changed:
            raise exc
        self.store.stats.escalations += 1
        self.store.stats.replays += 1

    def _static_eval(self, q: Query, mode: str):
        from repro.core.bigjoin import seed_tuples_for
        plan = self._static_plan(q)
        seed_rel = q.atoms[plan.seed_atom].rel
        seed = seed_tuples_for(plan,
                               {seed_rel: self.store.relation_rows(
                                   seed_rel)})
        indices = self.store.indices_for(plan)
        for attempt in range(_delta.DeltaBigJoin.MAX_ESCALATIONS + 1):
            s = self._sizing(q, None, None)  # re-read escalated floors
            out_cap = s.out_capacity if mode == "collect" else 1
            try:
                if self.local:
                    cfg = BigJoinConfig(batch=s.batch, seed_chunk=s.batch,
                                        mode=mode, out_capacity=out_cap)
                    return run_bigjoin(plan, indices, seed, cfg=cfg)
                from repro.core.distributed import (DistConfig,
                                                    get_distributed_program,
                                                    run_program)
                base = BigJoinConfig(batch=s.batch, seed_chunk=s.batch,
                                     mode=mode, out_capacity=out_cap)
                dcfg = DistConfig(base, self.w,
                                  route_capacity=s.route_capacity,
                                  balance=self.balance)
                program = get_distributed_program(plan, dcfg, self.mesh)
                return run_program(program, self.w, mode == "collect",
                                   indices, seed,
                                   np.ones(seed.shape[0], np.int32),
                                   width=plan.seed_width)
            except CapacityOverflow as exc:
                if attempt >= _delta.DeltaBigJoin.MAX_ESCALATIONS:
                    raise
                self._escalate_static(q, exc, s)
        raise AssertionError("unreachable")

    # -- introspection ------------------------------------------------------
    @property
    def edges(self) -> np.ndarray:
        """The live edge set (host truth)."""
        return self.store.edges

    @property
    def num_edges(self) -> int:
        return int(self.store.num_edges)  # O(1): no mirror materialization

    @property
    def stats(self) -> _delta.StoreStats:
        return self.store.stats

    def __repr__(self):  # pragma: no cover - debug aid
        where = "local" if self.local else f"{self.w}-worker mesh"
        return (f"GraphSession({self.num_edges:,} edges, "
                f"{len(self.handles)} queries, {where}, "
                f"epoch {self.epoch})")
