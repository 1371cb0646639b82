"""The correctness control: the reference in the program's place, with one
guarantee of the configuration broken.

The configuration promises per-batch set semantics: a re-insert of a live
edge and a delete of an absent one change nothing.  The control skips
normalization's probe of the live set, the step a change that removes the
normalize round trip would be tempted to drop: every edge whose weights in
the batch sum above zero counts as inserted and every one below zero as
deleted, live or not, and the triangle delta is taken through those edges.
The live set it keeps stays exact (it applies only what really changes), so
what the comparison has to catch is the wrong normalized sets and deltas.
The benchmark's own runs never run it; ``run.py --control`` does.
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np

from bench import cell, graph, traffic
from bench.reference import Reference

# rows the control serves after the warm-up: at least what a run of the
# cell answers in its window (51 s at 430-860 updates/s on one v5e)
WINDOW_ROWS = 51_000


class SkipNormalize:
    def __init__(self, keys: np.ndarray, nv: int):
        self.ref = Reference(keys, nv)
        self.epoch = 0

    def serve(self, rows: np.ndarray, w: np.ndarray, sent: cell.Sent):
        rows = np.asarray(rows, np.int64)
        w = np.asarray(w, np.int64)
        keep = (rows[:, 0] != rows[:, 1]) & (w != 0)
        keys, inv = np.unique(graph.pack(rows[keep, 0], rows[keep, 1]),
                              return_inverse=True)
        net = np.zeros(keys.size, np.int64)
        np.add.at(net, inv, w[keep])
        ins, dels = keys[net > 0], keys[net < 0]
        live_ins = self.ref.out.contains(ins)
        live_dels = self.ref.out.contains(dels)
        before = self.ref.triangles_through(dels)
        self.ref.apply(ins[~live_ins], dels[live_dels])
        after = self.ref.triangles_through(ins)
        self.epoch += 1
        sent.ins, sent.dels = ins, dels
        sent.count_delta = after - before
        sent.epoch = self.epoch
        sent.t_done = time.perf_counter()


def run_control(cfg: dict, mix: dict, seed: int,
                window_epochs: Optional[int] = None) -> dict:
    if window_epochs is None:
        window_epochs = -(-WINDOW_ROWS // int(mix["batch"]))
    keys = graph.rmat_graph(cfg, seed)
    nv = 1 << int(cfg["scale"])
    stream = traffic.make_stream(mix, keys, nv, seed)
    engine = SkipNormalize(keys, nv)
    sent = []
    for i in range(int(mix["warmup_batches"]) + window_epochs):
        rows, w = stream.next()
        s = cell.Sent(rows, w, time.perf_counter(), "window")
        engine.serve(rows, w, s)
        sent.append(s)
    checks, failed = cell.verify(keys, nv, sent, engine.ref.live_keys())
    return {"correct": all(v == 0 for v in checks.values()),
            "attempted": len(sent), "failed": failed,
            "control": "skip-normalize",
            "checks": {k: {"value": v, "limit": 0}
                       for k, v in checks.items()}}
