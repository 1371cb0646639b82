"""Serving drivers.

Two serving modes share this entry point:

**LM decode** (the original path): batched prefill + autoregressive decode
with a KV cache::

    python -m repro.launch.serve --arch gemma2-2b --batch 4 --steps 32

**Streaming subgraph monitoring** (the paper's deployment, §5.3): load a
graph into a :class:`repro.api.GraphSession`, register one or more standing
queries, then run the Delta-BiGJoin epoch loop ``normalize ->
dAQ_1..dAQ_n (every query) -> commit`` as edge updates stream in::

    python -m repro.launch.serve --stream --query triangle,diamond \
        --scale 10 --epochs 12 --batch-size 512

Every epoch applies one mixed insert/delete batch from
``data.synthetic.EdgeUpdateStream`` through the session — all registered
queries ride the SAME shared index regions and the same single commit (all
local devices are mesh workers; ``--local`` keeps the session on the host)
— and reports per-epoch latency and update/output-change throughput.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def serve_stream(args):
    """Single-tenant streaming monitor: a thin wrapper over the serving
    pool (DESIGN.md §9) — one tenant, coalesce=1, synchronous
    submit→result per logical epoch, so the printed per-epoch numbers mean
    exactly what the bespoke driver's used to.  The prep/apply pipeline,
    admission prewarm and (``--durable-dir``) WAL+snapshot durability all
    come from :class:`repro.serve.SessionPool` instead of bespoke code."""
    from repro.api import Graph, compilestats, oracle_count
    from repro.data.synthetic import EdgeUpdateStream, rmat_graph
    from repro.serve import SessionPool

    g = Graph.from_edges(rmat_graph(args.scale, args.edge_factor,
                                    seed=args.seed))
    names = [n.strip() for n in args.query.split(",") if n.strip()]
    # queries over the materialized ``tri`` relation (e.g. 4-clique-tri,
    # §5.4): a standing triangle query on the SAME session feeds the tri
    # relation — each logical epoch is then two session updates, edge batch
    # first, the resulting signed triangle delta second.  Registration and
    # tri seeding run inside the pool's admission ``setup`` hook so the
    # admission prewarm covers every standing query.
    state = {}

    def setup(session):
        handles = [session.register(n) for n in names]
        needs_tri = any(atom.rel == "tri"
                        for h in handles for atom in h.query.atoms)
        tri0 = None
        if needs_tri:
            feeder = session.register("triangle")
            tri0, _ = feeder.enumerate()
            session.add_relation("tri", tri0)
            if feeder not in handles:
                handles = [feeder] + handles
        state.update(handles=handles, needs_tri=needs_tri, tri0=tri0)

    pool = SessionPool(local=args.local, balance=args.balance,
                       update_batch=args.batch_size, prewarm=args.prewarm,
                       horizon=args.epochs * args.batch_size,
                       durable_dir=args.durable_dir,
                       snapshot_every=args.snapshot_every)
    t0 = time.time()
    tenant = pool.admit("stream", g.edges, setup=setup, coalesce=1,
                        batch=args.bprime, out_capacity=args.out_capacity)
    t_admit = time.time() - t0
    session = tenant.session
    handles, needs_tri, tri0 = \
        state["handles"], state["needs_tri"], state["tri0"]
    mode = "host-local" if session.local else (
        f"{session.w}-worker mesh" + (" (balanced)" if args.balance else ""))
    stream = EdgeUpdateStream(g.num_vertices, args.batch_size,
                              insert_frac=args.insert_frac,
                              skew=args.stream_skew, seed=args.seed + 1)
    print(f"monitoring {', '.join(names)} over {g.num_edges:,} edges on "
          f"{mode}; {args.epochs} epochs x {args.batch_size} updates "
          "(one shared commit per epoch"
          + (", tri relation fed by the standing triangle query)"
         if needs_tri else ")"))
    if args.prewarm:
        print(f"prewarm: walked the AOT capacity ladder in "
              f"{t_admit:.1f}s ({tenant.stats.prewarm_compiles} compile "
              "events"
              + (", persistent cache "
                 f"{compilestats.cache_dir()}" if compilestats.cache_dir()
                 else "") + ")")
    if args.durable_dir and session.epoch > 0:
        print(f"recovered epoch {session.epoch} from {args.durable_dir} "
              f"({tenant.stats.replayed} WAL epochs replayed)")

    times = []
    compiles = []
    noops = 0
    updates_sent = 0
    # the stream generator needs the live set to pick deletes; maintain it
    # incrementally from each epoch's normalized (ins, dels) instead of
    # pulling session.edges — the device-resident store's mirror would cost
    # an O(|E|) materialization per epoch otherwise
    live = session.edges
    for step in range(args.epochs):
        upd, wts = stream.batch_at(step, live=live)
        t0 = time.time()
        res = tenant.submit(upd, wts).result()
        updates_sent += 1
        res2 = None
        if needs_tri:
            td = res.deltas["triangle"]
            t_upd = td.tuples if td.tuples is not None else \
                np.zeros((0, 3), np.int32)
            t_w = td.weights if td.weights is not None else \
                np.zeros(0, np.int32)
            res2 = tenant.submit({"tri": (t_upd, t_w)}).result()
            updates_sent += 1
            noops += int(res2.is_noop)
        dt = max(time.time() - t0, 1e-9)  # no-op epochs can be ~0s
        live = res.advance(live)  # host bookkeeping outside the timer
        times.append(dt)
        compiles.append(res.compile_events +
                        (res2.compile_events if res2 is not None else 0))
        noops += int(res.is_noop)
        parts = []
        changes = 0
        for h in handles:
            # a logical epoch's delta is the sum over both session updates
            # (edge-fed queries fire on the first, tri-fed on the second)
            ds = [res.deltas[h.name]]
            if res2 is not None:
                ds.append(res2.deltas[h.name])
            cd = sum(d.count_delta for d in ds)
            chg = sum(0 if d.weights is None else int(np.abs(
                d.weights).sum()) for d in ds)
            changes += chg
            parts.append(f"{h.name} {cd:+,}")
        print(f"  epoch {step}: {'  '.join(parts)} "
              f"({changes:,} changes) in {dt*1e3:.0f} ms — "
              f"{upd.shape[0]/dt:,.0f} upd/s, {changes/dt:,.0f} changes/s")
    warm = times[2:] or times
    warm_compiles = sum(compiles[2:]) if len(compiles) > 2 else 0
    st = session.stats
    p50, p99 = np.percentile(times, [50, 99])
    print(f"steady state: {np.median(warm)*1e3:.0f} ms/epoch, "
          f"{args.batch_size/np.median(warm):,.0f} upd/s; net "
          + " ".join(f"{h.name} {h.net_change:+,}" for h in handles)
          + f"; {st.commit_calls} commits / {st.normalize_calls} "
          f"normalizes over {st.epochs} epochs")
    print(f"latency: p50 {p50*1e3:.1f} ms  p99 {p99*1e3:.1f} ms  max "
          f"{max(times)*1e3:.1f} ms (p99/p50 {p99/max(p50, 1e-9):.1f}x); "
          f"compile events: {st.prewarm_compiles} prewarm + "
          f"{sum(compiles)} streaming ({warm_compiles} after warmup)"
          + (f"; {compilestats.persistent_hits()} persistent-cache hits"
             if compilestats.cache_dir() else ""))

    if args.verify:
        rels_now = {"edge": session.edges}
        rels_0 = {"edge": g.edges}
        if needs_tri:
            rels_now["tri"] = session.relation("tri")
            rels_0["tri"] = tri0
        for h in handles:
            ref = oracle_count(h.query, rels_now)
            ref0 = oracle_count(h.query, rels_0)
            if h.net_change != ref - ref0:  # not assert: survives python -O
                raise RuntimeError(
                    f"{h.name}: maintained total {h.net_change} != "
                    f"recompute diff {ref - ref0}")
            print(f"verified {h.name}: maintained total == recompute diff "
                  f"({ref:,} instances now) ✓")
        # one normalize per update, one commit per NON-no-op epoch,
        # regardless of how many standing queries are registered
        if st.normalize_calls != updates_sent or \
                st.commit_calls != updates_sent - noops or \
                st.commit_calls != st.epochs:
            raise RuntimeError(
                f"epoch contract violated: {st.commit_calls} commits / "
                f"{st.normalize_calls} normalizes for {updates_sent} "
                f"updates ({noops} no-ops)")
    pool.close()
    return sum(h.net_change for h in handles)


def serve_concurrent(args):
    """N-tenant concurrent serving demo: one :class:`SessionPool`, one
    mesh, ``--concurrent`` tenants each monitoring its own graph + update
    stream from its own client thread.  Prints the pool's aggregate stats
    (latency percentiles, coalescing, backpressure sheds, snapshot/replay
    counters, serving compile budget); ``--verify`` recomputes every
    tenant's maintained total from scratch at the end."""
    import threading

    from repro.api import oracle_count
    from repro.data.synthetic import EdgeUpdateStream, rmat_graph
    from repro.serve import SessionPool

    names = [n.strip() for n in args.query.split(",") if n.strip()]
    # admission prewarm is non-optional here: the multi-tenant serving
    # contract (DESIGN.md §9) is zero serving-path compiles, which
    # --verify asserts below
    pool = SessionPool(local=args.local, balance=args.balance,
                       update_batch=args.batch_size, prewarm=True,
                       horizon=args.epochs * args.batch_size,
                       durable_dir=args.durable_dir,
                       snapshot_every=args.snapshot_every)
    graphs, tenants = {}, {}
    t0 = time.time()
    for i in range(args.concurrent):
        name = f"tenant{i}"
        graphs[name] = rmat_graph(args.scale, args.edge_factor,
                                  seed=args.seed + i)
        tenants[name] = pool.admit(
            name, graphs[name], queries=names, coalesce=args.coalesce,
            max_queue=args.max_queue, batch=args.bprime,
            out_capacity=args.out_capacity)
    mode = "host-local" if pool.local else "mesh"
    print(f"admitted {len(tenants)} tenants ({', '.join(names)} each) on "
          f"one {mode} pool in {time.time()-t0:.1f}s; {args.epochs} epochs "
          f"x {args.batch_size} updates per tenant")

    # materialize each tenant's live mirror + epoch on THIS thread, before
    # any
    # client submits: session.edges runs a jitted device fold, and all
    # device work must stay off the client threads once the pool's apply
    # dispatcher is live (DESIGN.md §9)
    live0 = {name: tenants[name].session.edges for name in tenants}
    starts = {name: tenants[name].session.epoch for name in tenants}

    def client(name):
        # balanced stream (insert_frac 0.5): live set stays within its
        # pow2 base rung, so the zero-compile serving budget holds
        stream = EdgeUpdateStream(
            1 << args.scale, args.batch_size, insert_frac=args.insert_frac,
            skew=args.stream_skew,
            seed=args.seed + 1 + len(tenants) + int(name[6:]))
        live = live0[name]
        start = starts[name]  # >0 after durable recovery
        for step in range(start, args.epochs):
            upd, wts = stream.batch_at(step, live=live)
            ticket = tenants[name].submit(upd, wts)
            if ticket is None:
                continue  # shed by backpressure
            live = ticket.result().advance(live)

    threads = [threading.Thread(target=client, args=(n,), daemon=True)
               for n in tenants]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    pool.drain()
    stats = pool.stats()
    print(stats.render())
    if args.verify:
        for name, handle in tenants.items():
            for h in handle.session.handles.values():
                ref = oracle_count(h.query, {"edge": handle.session.edges})
                ref0 = oracle_count(h.query, {"edge": graphs[name]})
                if h.net_change != ref - ref0:
                    raise RuntimeError(
                        f"{name}/{h.name}: maintained total "
                        f"{h.net_change} != recompute diff {ref - ref0}")
            print(f"verified {name}: maintained totals == recompute ✓")
        if stats.serve_compiles:
            raise RuntimeError(
                f"{stats.serve_compiles} serving-path compile events "
                "(admission prewarm must cover the whole stream)")
    pool.close()
    return stats


def serve_lm(args):
    from repro.configs import get_arch
    from repro.models import transformer as T

    spec = get_arch(args.arch)
    assert spec.family == "lm", "serve.py drives the LM archs"
    cfg = spec.full_config if args.full else spec.smoke_config
    params = T.init(jax.random.PRNGKey(args.seed), cfg)

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    max_len = args.prompt_len + args.steps

    prefill = jax.jit(lambda p, t: T.prefill(p, t, cfg))
    decode = jax.jit(lambda p, c, t, pos: T.decode_step(p, c, t, pos, cfg),
                     donate_argnums=(1,))

    t0 = time.time()
    logits, pcache = prefill(params, prompts)
    # right-size the cache: copy prefill K/V into a max_len cache
    cache = T.make_cache(cfg, args.batch, max_len)
    cache = {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], pcache["k"].astype(cache["k"].dtype),
            (0, 0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], pcache["v"].astype(cache["v"].dtype),
            (0, 0, 0, 0, 0)),
    }
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    print(f"prefill {args.prompt_len} tokens in {time.time()-t0:.2f}s")

    out = [tok]
    t0 = time.time()
    for s in range(args.steps - 1):
        pos = jnp.asarray(args.prompt_len + s, jnp.int32)
        logits, cache = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    dt = (time.time() - t0) / max(args.steps - 1, 1)
    toks = np.concatenate([np.asarray(t) for t in out], 1)
    print(f"decode: {dt*1e3:.1f} ms/step, {args.batch/dt:,.1f} tok/s "
          f"aggregate; sample: {toks[0][:16].tolist()}")
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    return toks


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="LM arch to serve (decode mode)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # streaming subgraph monitor mode
    ap.add_argument("--stream", action="store_true",
                    help="serve a streaming subgraph monitor instead of an "
                    "LM (distributed Delta-BiGJoin epoch loop)")
    ap.add_argument("--query", default="triangle",
                    help="comma list of named queries to monitor on ONE "
                    "shared session (stream mode)")
    ap.add_argument("--scale", type=int, default=10,
                    help="rmat scale of the base graph (stream mode)")
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=512,
                    help="updates per epoch (stream mode)")
    ap.add_argument("--insert-frac", type=float, default=0.75)
    ap.add_argument("--stream-skew", type=float, default=0.0,
                    help="zipf exponent for insert endpoints (0 = uniform)")
    ap.add_argument("--bprime", type=int, default=2048,
                    help="B' per-worker proposal budget (stream mode)")
    ap.add_argument("--out-capacity", type=int, default=1 << 20)
    ap.add_argument("--balance", action="store_true",
                    help="BiGJoin-S Balance operator (stream mode)")
    ap.add_argument("--local", action="store_true",
                    help="host-local DeltaBigJoin baseline (stream mode)")
    ap.add_argument("--prewarm", action="store_true",
                    help="walk the AOT capacity ladder before the first "
                    "epoch so warm epochs trigger zero XLA compiles "
                    "(stream mode; pairs with the persistent compile cache)")
    ap.add_argument("--verify", action="store_true",
                    help="check the maintained total against full "
                    "recomputation at the end (stream mode)")
    # concurrent serving (DESIGN.md §9): N tenants on one SessionPool
    ap.add_argument("--concurrent", type=int, default=0, metavar="N",
                    help="serve N tenants concurrently on one pool "
                    "(implies --stream semantics per tenant)")
    ap.add_argument("--coalesce", type=int, default=8,
                    help="max queued batches folded into one device epoch "
                    "per tenant (concurrent mode)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="per-tenant ingest queue bound — full queues "
                    "backpressure their own client only")
    ap.add_argument("--durable-dir", default=None,
                    help="WAL + snapshot directory: crash-killed serves "
                    "restore the last snapshot and replay the log "
                    "bit-exactly on restart")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="snapshot cadence in epochs (with --durable-dir)")
    args = ap.parse_args(argv)

    if args.concurrent:
        return serve_concurrent(args)
    if args.stream:
        return serve_stream(args)
    if not args.arch:
        ap.error("--arch is required unless --stream is given")
    return serve_lm(args)


if __name__ == "__main__":
    main()
