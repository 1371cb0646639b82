#!/usr/bin/env python3
"""Bring-up smoke of the served subgraph monitor on a TPU.

Drives the path ``python -m repro.launch.serve --stream`` serves — a
``SessionPool`` tenant admitted with its prewarm, then one
``tenant.submit(...).result()`` per epoch — over an R-MAT graph with the
Graph500 parameters (a=.57, b=.19, c=.19, edge factor 16), with a standing
``triangle`` query and a mixed-sign update stream.  Every epoch's signed
triangle delta must equal an independent numpy reference exactly: the
triangles, before and after the batch, that contain at least one of the
batch's normalized edges, found by neighbour-set intersection on a host CSR
(O(|Δ|·degree); the reference imports nothing from ``repro``).  A second,
short phase runs ``4-clique-tri`` — composite (hi, lo) keys — at a small
scale with the full recompute check.

    python3 chip_smoke.py               # one chip: both phases
    python3 chip_smoke.py --chips 4     # the triangle stream on a 4-device
                                        # mesh SessionPool, nothing else
                                        # (2 epochs, no admission prewarm)

Earlier lines report the graph size, admission/prewarm seconds, compile
events, per-epoch wall time (a smoke timing, not a metric), peak device
bytes and which path each kernel family took.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``.  Any failed check exits non-zero;
without a TPU the script exits non-zero before printing any result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# numpy reference: live directed edge set + triangles through given edges
# ---------------------------------------------------------------------------

def _pack(u, v) -> np.ndarray:
    return (np.asarray(u, np.int64) << 32) | np.asarray(v, np.int64)


class ReferenceGraph:
    """The live edge set as a static CSR of the initial edges (both
    directions) plus a small overlay of the edges the stream inserted and
    deleted since.  Triangle semantics follow the query
    ``tri(a, b, c) :- e(a, b), e(b, c), e(a, c)`` on directed edges."""

    def __init__(self, edges: np.ndarray):
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        self.nv = int(e.max()) + 1 if e.size else 0
        self.packed = np.sort(_pack(e[:, 0], e[:, 1]))
        self.out_ptr, self.out_idx = self._csr(self.packed)
        self.in_ptr, self.in_idx = self._csr(np.sort(_pack(e[:, 1],
                                                           e[:, 0])))
        self.added: set = set()
        self.removed: set = set()
        self.nbr_add = {"out": {}, "in": {}}
        self.nbr_rem = {"out": {}, "in": {}}

    def _csr(self, packed: np.ndarray):
        """Row pointers and neighbour lists from sorted (row<<32|col)."""
        ptr = np.zeros(self.nv + 1, np.int64)
        np.cumsum(np.bincount(packed >> 32, minlength=self.nv), out=ptr[1:])
        return ptr, packed & 0xFFFFFFFF

    def has(self, key: int) -> bool:
        if key in self.added:
            return True
        if key in self.removed:
            return False
        i = np.searchsorted(self.packed, key)
        return bool(i < self.packed.size and self.packed[i] == key)

    def nbrs(self, x: int, way: str) -> np.ndarray:
        ptr, idx = ((self.out_ptr, self.out_idx) if way == "out"
                    else (self.in_ptr, self.in_idx))
        base = idx[ptr[x]:ptr[x + 1]] if x < self.nv else idx[:0]
        rem = self.nbr_rem[way].get(x)
        if rem:
            base = base[~np.isin(base, np.fromiter(rem, np.int64))]
        add = self.nbr_add[way].get(x)
        if add:
            base = np.union1d(base, np.fromiter(add, np.int64))
        return base

    def normalize(self, rows: np.ndarray, w: np.ndarray):
        """Net a dirty signed batch against the live set: self-loops and
        zero weights drop; a distinct edge with positive net weight is
        inserted if absent, with negative net weight deleted if live."""
        rows = np.asarray(rows, np.int64).reshape(-1, 2)
        w = np.asarray(w, np.int64)
        keep = (rows[:, 0] != rows[:, 1]) & (w != 0)
        keys, inv = np.unique(_pack(rows[keep, 0], rows[keep, 1]),
                              return_inverse=True)
        net = np.bincount(inv, weights=w[keep], minlength=keys.size)
        live = np.array([self.has(int(k)) for k in keys], bool)
        ins, dels = keys[(net > 0) & ~live], keys[(net < 0) & live]
        return ins, dels

    def apply(self, ins: np.ndarray, dels: np.ndarray) -> None:
        for key, on in [(int(k), True) for k in ins] + \
                       [(int(k), False) for k in dels]:
            u, v = key >> 32, key & 0xFFFFFFFF
            (self.added if on else self.removed).add(key)
            (self.removed if on else self.added).discard(key)
            for way, a, b in (("out", u, v), ("in", v, u)):
                mine = self.nbr_add if on else self.nbr_rem
                other = self.nbr_rem if on else self.nbr_add
                mine[way].setdefault(a, set()).add(b)
                other[way].get(a, set()).discard(b)

    def triangles_through(self, keys: np.ndarray) -> int:
        """Distinct triangles of the live graph containing >= 1 of the
        (live) edges ``keys``: each edge (x, y) as (a, b), (b, c), (a, c)."""
        found = []
        for key in keys:
            x, y = int(key) >> 32, int(key) & 0xFFFFFFFF
            ox, oy = self.nbrs(x, "out"), self.nbrs(y, "out")
            ix, iy = self.nbrs(x, "in"), self.nbrs(y, "in")
            c = np.intersect1d(ox, oy, assume_unique=True)
            a = np.intersect1d(ix, iy, assume_unique=True)
            b = np.intersect1d(ox, iy, assume_unique=True)
            found += [np.stack([np.full_like(c, x), np.full_like(c, y), c], 1),
                      np.stack([a, np.full_like(a, x), np.full_like(a, y)], 1),
                      np.stack([np.full_like(b, x), b, np.full_like(b, y)], 1)]
        if not found:
            return 0
        return int(np.unique(np.concatenate(found), axis=0).shape[0])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

class CompileMeter:
    """Seconds JAX spent in backend (XLA/Mosaic) compiles, from its own
    monitoring events, so set-up time splits into compiling and the rest."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1
            if self.count % 20 == 0:  # progress, should a phase overrun
                log(f"  ... {self.count} backend compiles, "
                    f"{self.seconds:.1f}s compiling")

    def since(self, mark) -> str:
        return (f"{self.count - mark[0]} backend compiles, "
                f"{self.seconds - mark[1]:.1f}s compiling")

    def mark(self):
        return (self.count, self.seconds)


def kernel_paths(session) -> dict:
    """Which path each kernel family took on the warm serving path, and
    why (``repro.kernels`` static choice + the traced coverage)."""
    from repro.kernels import FAMILIES, OFF_DEFAULT_PATH
    from repro.kernels.intersect.ops import default_interpret
    cov = session.kernel_coverage()
    paths = {}
    for fam in FAMILIES:
        if fam in OFF_DEFAULT_PATH:
            paths[fam] = {"path": "jnp", "why": "off the default path: "
                          + OFF_DEFAULT_PATH[fam]}
    probes = {rel: (c["probe_pallas_calls"], c["probe_mosaic"])
              for rel, c in cov.items()}
    mosaic = bool(probes) and all(n >= 1 and m for n, m in probes.values())
    paths["member"] = {
        "path": "compiled" if mosaic else (
            "interpret" if default_interpret() else "jnp"),
        "why": "warm probe per relation (pallas_calls, lowers to Mosaic): "
               + json.dumps(probes)}
    folds = {rel: c["fold_pallas_calls"] for rel, c in cov.items()}
    fold = paths.setdefault("fold", {
        "path": "kernel" if folds and all(folds.values()) else "jnp",
        "why": "on the default path"})
    fold["why"] += f"; traced fold launches per relation {folds}"
    return paths


def triangle_phase(args, pool_kwargs, label: str, meter: CompileMeter,
                   prewarm: bool = True):
    """The triangle stream against the numpy reference.  With ``prewarm``
    the admission walks the compile ladder and no epoch after the first
    may compile; without, the stream compiles as it goes (``serve
    --stream`` without ``--prewarm``).  Returns the session's kernel
    paths and the pool (closed by the caller)."""
    import jax

    from repro.data.synthetic import EdgeUpdateStream, rmat_graph
    from repro.serve import SessionPool

    t0 = time.time()
    edges = rmat_graph(args.scale, args.edge_factor, seed=args.seed)
    nv = int(edges.max()) + 1
    log(f"[{label}] graph: R-MAT scale {args.scale}, edge factor "
        f"{args.edge_factor} ({args.edge_factor << args.scale:,} generated "
        f"edges), {edges.shape[0]:,} distinct edges over {nv:,} vertices "
        f"({time.time() - t0:.1f}s to generate)")
    t0 = time.time()
    ref = ReferenceGraph(edges)
    log(f"[{label}] numpy reference CSR built in {time.time() - t0:.1f}s")

    pool = SessionPool(update_batch=args.batch_size, prewarm=prewarm,
                       horizon=args.epochs * args.batch_size, **pool_kwargs)
    t0, mark = time.time(), meter.mark()
    built = {}

    def setup(session):  # runs after the store is built, before prewarm
        session.register("triangle")
        built.update(t=time.time(), mark=meter.mark())
        log(f"[{label}] store built in {built['t'] - t0:.1f}s "
            f"({meter.since(mark)})" + ("; prewarming" if prewarm else ""))

    tenant = pool.admit("smoke", edges, setup=setup, coalesce=1,
                        batch=args.bprime, out_capacity=args.out_capacity)
    session = tenant.session
    mode = "host-local" if session.local else f"{session.w}-device mesh"
    if prewarm:
        log(f"[{label}] admission prewarm: {time.time() - built['t']:.1f}s "
            f"({tenant.stats.prewarm_compiles} compile events, "
            f"{meter.since(built['mark'])}) on {mode}")
    else:
        log(f"[{label}] admitted on {mode}, no prewarm: epochs compile "
            "what they use")
    if not session.local:
        log(f"[{label}] bytes in use per device after admission: "
            + json.dumps(device_bytes(jax.devices()[:session.w])))

    # deletes are drawn from the initial edge list: an edge an earlier
    # epoch already deleted becomes an absent-edge delete, which normalize
    # must net out like any other dirty row
    stream = EdgeUpdateStream(nv, args.batch_size,
                              insert_frac=args.insert_frac,
                              seed=args.seed + 1)
    warm_compiles = 0
    for step in range(args.epochs):
        upd, w = stream.batch_at(step, live=edges)
        ins, dels = ref.normalize(upd, w)
        before = ref.triangles_through(dels)
        t0 = time.time()
        res = tenant.submit(upd, w).result()
        dt = time.time() - t0
        ref.apply(ins, dels)
        after = ref.triangles_through(ins)
        got = res.deltas["triangle"].count_delta
        want = after - before
        check(np.array_equal(np.sort(_pack(res.ins[:, 0], res.ins[:, 1])),
                             ins) and
              np.array_equal(np.sort(_pack(res.dels[:, 0], res.dels[:, 1])),
                             dels),
              f"epoch {step}: normalized batch differs from the reference")
        check(got == want, f"epoch {step}: triangle delta {got:+,} != "
              f"reference {want:+,}")
        if step > 0:
            warm_compiles += res.compile_events
        log(f"[{label}] epoch {step}: {ins.size} ins / {dels.size} dels, "
            f"triangle delta {got:+,} == reference ({after:,} after - "
            f"{before:,} before); {res.compile_events} compile events; "
            f"smoke timing {dt * 1e3:.0f} ms (not a metric)")
    log(f"[{label}] compile events in epochs 1..: {warm_compiles}; whole "
        f"phase so far: {meter.since((0, 0.0))}")
    if prewarm:
        check(warm_compiles == 0, f"{warm_compiles} compiles after warm-up")
    return kernel_paths(session), pool


def composite_phase(args, meter: CompileMeter):
    """4-clique-tri at a small scale: the composite-key (hi, lo) path, with
    the full recompute check of ``serve --verify``.  Like ``serve --stream``
    without ``--prewarm``, it compiles what the stream uses as it goes."""
    from repro.api import oracle_count
    from repro.data.synthetic import EdgeUpdateStream, rmat_graph
    from repro.serve import SessionPool

    edges = rmat_graph(args.composite_scale, 8, seed=args.seed)
    nv = int(edges.max()) + 1
    state = {}

    def setup(session):
        feeder = session.register("triangle")
        tri0, _ = feeder.enumerate()
        session.add_relation("tri", tri0)
        state.update(clique=session.register("4-clique-tri"), tri0=tri0)

    batch = 64
    pool = SessionPool(update_batch=batch, prewarm=False)
    t0, mark = time.time(), meter.mark()
    tenant = pool.admit("composite", edges, setup=setup, coalesce=1)
    session = tenant.session
    log(f"[composite] 4-clique-tri over R-MAT scale {args.composite_scale} "
        f"({edges.shape[0]:,} edges, {state['tri0'].shape[0]:,} initial "
        f"triangles); admission {time.time() - t0:.1f}s "
        f"({meter.since(mark)})")
    stream = EdgeUpdateStream(nv, batch, insert_frac=args.insert_frac,
                              seed=args.seed + 1)
    live = session.edges
    for step in range(args.composite_epochs):
        upd, w = stream.batch_at(step, live=live)
        res = tenant.submit(upd, w).result()
        td = res.deltas["triangle"]
        rows = td.tuples if td.tuples is not None else \
            np.zeros((0, 3), np.int32)
        tw = td.weights if td.weights is not None else np.zeros(0, np.int32)
        res2 = tenant.submit({"tri": (rows, tw)}).result()
        live = res.advance(live)
        log(f"[composite] epoch {step}: 4-clique-tri "
            f"{res.deltas['4-clique-tri'].count_delta + res2.deltas['4-clique-tri'].count_delta:+,}")
    h = state["clique"]
    now = oracle_count(h.query, {"edge": session.edges,
                                 "tri": session.relation("tri")})
    then = oracle_count(h.query, {"edge": edges, "tri": state["tri0"]})
    check(h.net_change == now - then,
          f"4-clique-tri maintained total {h.net_change:+,} != recompute "
          f"diff {now - then:+,}")
    log(f"[composite] verified: maintained total {h.net_change:+,} == "
        f"recompute diff ({now:,} instances now); phase: "
        f"{time.time() - t0:.1f}s, {meter.since(mark)}")
    paths = kernel_paths(session)
    tri = session.kernel_coverage().get("tri", {})
    check(tri.get("composite") and tri.get("probe_mosaic"),
          f"composite tri probe did not run the compiled kernel: {tri}")
    pool.close()
    return paths


def mem_stats(d) -> dict:
    stats = d.memory_stats()
    check(stats is not None, f"device {d} reports no memory stats")
    return stats or {}


def device_bytes(devices, key: str = "bytes_in_use") -> dict:
    return {f"{d.platform}:{d.id}": mem_stats(d).get(key, 0)
            for d in devices}


def report_paths(label: str, paths: dict) -> None:
    for fam, p in paths.items():
        log(f"[{label}] kernel {fam}: {p['path']} — {p['why']}")


def run(args) -> dict:
    """All phases; raises SmokeFailure (or the engine's error) on any
    failed check.  Returns the device record of the last line."""
    import jax
    from jax.sharding import Mesh

    from repro.core.compilestats import cache_dir
    from repro.core.distributed import AXIS

    meter = CompileMeter()
    devices = jax.devices()
    log(f"devices: {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); compile cache: {cache_dir()}")
    if args.chips == 4:
        check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
              f"{len(devices)}")
        mesh = Mesh(np.array(devices[:4]), (AXIS,))
        # no admission prewarm: its ladder holds ~21 mesh programs of
        # ~23 s each to compile for v5e 2x2 (AOT), more than the mesh
        # check needs; the stream compiles the few it reaches
        paths, pool = triangle_phase(args, {"local": False, "mesh": mesh},
                                     "mesh4", meter, prewarm=False)
        report_paths("mesh4", paths)
        check(paths["member"]["path"] == "compiled",
              "member kernel did not run compiled")
        log("[mesh4] bytes in use per device: "
            + json.dumps(device_bytes(devices[:4])))
        log("[mesh4] peak_bytes_in_use per device: " + json.dumps(
            device_bytes(devices[:4], "peak_bytes_in_use")))
        pool.close()
    else:
        # the serve path's own choice: one device -> the host-local engine
        paths, pool = triangle_phase(args, {}, "triangle", meter)
        report_paths("triangle", paths)
        check(paths["member"]["path"] == "compiled",
              "member kernel did not run compiled")
        peak = mem_stats(devices[0]).get("peak_bytes_in_use", 0)
        log(f"[triangle] peak_bytes_in_use: {peak:,}")
        pool.close()
        del pool
        report_paths("composite", composite_phase(args, meter))
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    # scale 22 (2^26 generated edges) did not finish a cold admission in
    # 20 minutes on one v5e (PERF.md); 18 is the default until cold
    # admission gets cheaper
    ap.add_argument("--scale", type=int, default=18)
    ap.add_argument("--edge-factor", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=None,
                    help="update epochs (default 8; 2 with --chips 4)")
    ap.add_argument("--batch-size", type=int, default=2048)
    ap.add_argument("--insert-frac", type=float, default=0.75)
    ap.add_argument("--bprime", type=int, default=2048)
    ap.add_argument("--out-capacity", type=int, default=1 << 20)
    ap.add_argument("--composite-scale", type=int, default=8)
    ap.add_argument("--composite-epochs", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.epochs is None:
        args.epochs = 2 if args.chips == 4 else 8

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        # JAX falls back to the CPU when the TPU cannot be opened: refuse
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 2
    try:
        device = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
