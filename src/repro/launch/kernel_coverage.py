"""CI kernel-coverage gate: warm composite stream, fused kernels on path.

Drives the §5.4 two-session pipeline (triangle feeder -> streamed ``tri``
relation -> standing 4-clique-tri) with the AOT prewarm ladder, then
asserts the two halves of the PR-10 contract:

- **zero serving compiles**: after ``prewarm``, every epoch reports
  ``EpochResult.compile_events == 0`` — the composite fused-fold path
  reuses the warmed jit cache, it does not fork new signatures;
- **composite kernels on the dispatch path**: ``GraphSession.
  kernel_coverage()`` shows, for the composite ``tri`` relation, >= 1
  ``pallas_call`` in the versioned probe, and in the commit fold exactly
  ONE fused launch when the fold family is on the default path
  (``repro.kernels.on_default_path``) and none when it is off — the
  launches a warm epoch actually executes.

Prints one JSON line (machine-readable for the CI heredoc) and exits
non-zero on any violation.  Run:

    PYTHONPATH=src python -m repro.launch.kernel_coverage \
        [--scale 8] [--epochs 6] [--batch-size 64]
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=8,
                    help="graph scale: nv = 2**scale")
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--update-batch", type=int, default=0,
                    help="pinned delta mark; 0 = 4x batch-size (triangle "
                    "deltas fan out past the edge batch that caused them)")
    args = ap.parse_args(argv)
    update_batch = args.update_batch or 4 * args.batch_size

    from repro.api import GraphSession
    from repro.data.synthetic import EdgeUpdateStream, uniform_graph
    from repro.kernels import on_default_path

    # nv*3 edges sit MID-rung (cap 4·nv) and the stream churns balanced
    # (insert_frac=0.5): the gate measures kernel coverage at steady state,
    # so the live sets must not random-walk across a base rung mid-stream —
    # a rung crossing recompiles by design (DESIGN.md §8), which would
    # mask a real coverage regression behind a capacity artifact.
    nv = 1 << args.scale
    edges = uniform_graph(nv, nv * 3, seed=7)
    sess = GraphSession(edges, local=True, batch=1024,
                        out_capacity=1 << 16, update_batch=update_batch)
    tri = sess.register("triangle")
    tri0, _ = tri.enumerate()
    sess.add_relation("tri", tri0)
    sess.register("4-clique-tri")
    prewarm_compiles = sess.prewarm(
        horizon=(args.warmup + args.epochs) * update_batch)

    stream = EdgeUpdateStream(nv, args.batch_size, insert_frac=0.5, seed=11)
    live = sess.edges
    warm_compiles, epoch_compiles = 0, []
    for step in range(args.warmup + args.epochs):
        upd, w = stream.batch_at(step, live=live)
        res = sess.update(upd, w)
        live = res.advance(live)
        d = res.deltas["triangle"]
        t_upd = d.tuples if d.tuples is not None else \
            np.zeros((0, 3), np.int32)
        t_w = d.weights if d.weights is not None else np.zeros(0, np.int32)
        res2 = sess.update({"tri": (t_upd, t_w)})
        ev = res.compile_events + res2.compile_events
        epoch_compiles.append(ev)
        if step >= args.warmup:
            warm_compiles += ev

    cov = sess.kernel_coverage()
    composite = {rel: c for rel, c in cov.items() if c["composite"]}
    rec = {
        "gate": "kernel_coverage",
        "prewarm_compiles": int(prewarm_compiles),
        "warm_compiles": int(warm_compiles),
        "epoch_compiles": epoch_compiles,
        "coverage": cov,
        "composite_relations": sorted(composite),
    }
    failures = []
    if warm_compiles != 0:
        failures.append(f"serving compiles after warmup: {warm_compiles}")
    if not composite:
        failures.append("no composite relation in the stream")
    want_fold = 1 if on_default_path("fold") else 0
    for rel, c in composite.items():
        if c["fold_pallas_calls"] != want_fold:
            failures.append(
                f"{rel}: commit fold traces {c['fold_pallas_calls']} "
                f"pallas_calls, want {want_fold}")
        if c["probe_pallas_calls"] < 1:
            failures.append(f"{rel}: no pallas launch in the probe path")
    rec["ok"] = not failures
    rec["failures"] = failures
    print(json.dumps(rec))
    print(f"kernel-coverage: {warm_compiles} serving compiles after "
          f"warmup; composite fold launches: "
          f"{ {r: c['fold_pallas_calls'] for r, c in composite.items()} }; "
          f"{'OK' if not failures else 'FAILED: ' + '; '.join(failures)}",
          file=sys.stderr)
    return 0 if not failures else 1


if __name__ == "__main__":
    raise SystemExit(main())
