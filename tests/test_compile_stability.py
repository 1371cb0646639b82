"""Compilation-stability lockdown (ISSUE 6, DESIGN.md §8).

Three layers of the latency-tail contract:

- the :class:`repro.core.capacity.Ratchet` quantizer itself — a fixed,
  history-independent geometric ladder with ratcheting (never-shrinking)
  per-key marks, so prewarm can enumerate exactly the shapes a stream
  will request;
- the streaming contract — after ``GraphSession.prewarm`` an adversarial
  batch-size stream that straddles every pow2 bucket and repeatedly
  crosses committed-region rungs triggers ZERO XLA compiles, local and
  mesh alike (``EpochResult.compile_events == 0`` every epoch);
- the persistent cross-process cache (``JAX_COMPILATION_CACHE_DIR``) — a second
  process walking the same ladder compiles nothing: every lowering is a
  cache hit and the cache gains no new entries.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import GraphSession, Ratchet, pow2_capacity
from repro.core import compilestats

# batch sizes straddle the 128/512 pow2 buckets and, cumulatively, walk
# the committed region across rungs (128 -> 512 -> 2048) several times
CROSSING_SIZES = [3, 120, 129, 257, 400, 511, 500, 64, 512, 1]


def _start_edges(nv=400, ne=1500, seed=11):
    from repro.data.synthetic import uniform_graph
    return uniform_graph(nv, ne, seed)


def _churn_batch(rng, live, size):
    """Churn-balanced batch: half deletes drawn from the live set so the
    base region stays on its pow2 rung (net growth would legitimately
    force a base-regrowth recompile, which this test is not about)."""
    k = min(size // 2, live.shape[0])
    sel = rng.choice(live.shape[0], k, replace=False)
    dels = live[sel]
    ins = rng.integers(0, 400, (size - k, 2))
    upd = np.concatenate([ins, dels]).astype(np.int32)
    w = np.concatenate([np.ones(size - k, np.int32),
                        -np.ones(k, np.int32)])
    return upd, w


# ---------------------------------------------------------------------------
# Ratchet unit tests
# ---------------------------------------------------------------------------

def test_ratchet_quantize_fixed_ladder():
    r = Ratchet(factor=4)
    base = pow2_capacity(1)  # the SEG floor anchors the ladder
    assert r.quantize(1) == base
    assert r.quantize(base) == base
    assert r.quantize(base + 1) == base * 4
    assert r.quantize(4 * base + 1) == base * 16
    # history independence: the rung depends only on the count
    assert Ratchet(factor=4).quantize(base + 1) == base * 4


def test_ratchet_capacity_never_shrinks():
    r = Ratchet()
    big = r.capacity("k", 1000)
    assert r.capacity("k", 5) == big  # smaller count keeps the mark
    assert r.capacity("k", 10 * 1000) > big  # larger count grows it
    assert r.peek("k") == r.capacity("k", 1)


def test_ratchet_observe_pins_and_floors():
    r = Ratchet()
    r.observe("k", 300)  # a pinned mark need not be a canonical rung
    assert r.peek("k") == 300
    assert r.capacity("k", 200) == 300  # under the pin: pinned shape wins
    over = r.capacity("k", 400)  # over the pin: canonical rung resumes
    assert over == max(r.quantize(400), 300)
    r.observe("k", 10)  # observe only floors, never lowers
    assert r.peek("k") >= over


def test_ratchet_reset_and_rungs():
    r = Ratchet(factor=4)
    base = pow2_capacity(1)
    r.capacity("a", 1000), r.capacity("b", 1)
    r.reset("a")
    assert r.peek("a") == 0 and r.peek("b") == base
    r.reset()
    assert r.marks() == {}
    assert r.rungs(1, 4 * base + 1) == [base, 4 * base, 16 * base]
    assert r.rungs(base + 1, base + 1) == [4 * base]
    assert r.next_rung(base) == 4 * base
    assert r.next_rung(base - 1) == base
    assert Ratchet(factor=2).rungs(1, 2 * base) == [base, 2 * base]


def test_ratchet_factor_validation():
    for bad in (0, 1, 3, 6, -4):
        with pytest.raises(ValueError):
            Ratchet(factor=bad)


# ---------------------------------------------------------------------------
# zero-recompile streaming contract
# ---------------------------------------------------------------------------

def _run_crossing_stream(session):
    session.register("triangle")
    spent = session.prewarm(horizon=sum(CROSSING_SIZES) * 4)
    assert spent > 0  # the ladder actually compiled something
    assert session.stats.prewarm_compiles == spent
    after_prewarm = session.stats.compile_events
    rng = np.random.default_rng(7)
    live = session.edges
    events = []
    for size in CROSSING_SIZES * 2:  # two passes: re-cross after compaction
        upd, w = _churn_batch(rng, live, size)
        res = session.update(upd, w)
        events.append(res.compile_events)
        live = res.advance(live)
    assert sum(events) == 0, \
        f"prewarmed stream recompiled: per-epoch events {events}"
    # store-level counter stayed FLAT across the whole stream
    assert session.stats.compile_events == after_prewarm


def test_zero_recompiles_after_prewarm_local():
    session = GraphSession(_start_edges(), local=True, batch=512,
                           out_capacity=1 << 16, update_batch=512)
    _run_crossing_stream(session)


@pytest.mark.slow
def test_zero_recompiles_after_prewarm_mesh():
    import jax
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices (XLA_FLAGS host platform count)")
    session = GraphSession(_start_edges(), local=False, batch=512,
                           out_capacity=1 << 16, update_batch=512)
    _run_crossing_stream(session)


def test_epoch_result_reports_compile_events():
    """Without prewarm the FIRST epoch must report its compiles — the
    counter is the observability half of the contract."""
    session = GraphSession(_start_edges(nv=64, ne=200, seed=3), local=True,
                           batch=128, out_capacity=1 << 14, update_batch=64)
    session.register("triangle")
    rng = np.random.default_rng(0)
    upd, w = _churn_batch(rng, session.edges, 32)
    res = session.update(upd, w)
    assert res.compile_events > 0


# ---------------------------------------------------------------------------
# persistent cross-process compile cache
# ---------------------------------------------------------------------------

_CHILD = """
import json, os
import numpy as np
from repro.core import compilestats
from repro.core.delta import RegionStore

rng = np.random.default_rng(0)
edges = np.unique(rng.integers(0, 60, (200, 2), dtype=np.int32), axis=0)
store = RegionStore(edges, device_resident=True)
store.ensure("edge", (0,), 1)
store.prewarm_folds(16, horizon=32)
d = compilestats.cache_dir()
entries = sum(len(fs) for _, _, fs in os.walk(d))
print(json.dumps({"compiles": compilestats.total(),
                  "hits": compilestats.persistent_hits(),
                  "entries": entries}))
"""


@pytest.mark.slow
def test_persistent_cache_second_process_compiles_nothing(tmp_path):
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")

    def run():
        out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    r1, r2 = run(), run()
    assert r1["entries"] > 0  # first process populated the cache
    assert r2["compiles"] == r1["compiles"]  # same ladder, same traces
    assert r2["hits"] > 0  # second process deserialized instead of
    assert r2["entries"] == r1["entries"]  # compiling: no new entries


def test_enable_persistent_cache_is_stable(monkeypatch):
    """Importing the package enabled the cache where the environment says,
    and re-enabling is idempotent — flipping jax's global cache config
    mid-process is reserved for process start (package import)."""
    import jax
    before = compilestats.cache_dir()
    assert before == compilestats.cache_dir_for(os.environ)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.setenv(compilestats.ENV_VAR, "/nonexistent/elsewhere")
    assert compilestats.enable_persistent_cache() == before
    assert compilestats.cache_dir() == before  # unchanged
