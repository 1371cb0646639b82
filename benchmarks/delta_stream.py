"""Streaming Delta-BiGJoin throughput -> BENCH_delta_stream.json.

Drives the distributed maintenance engine through a subprocess per worker
count (the XLA host-device override must be set before jax initializes), so
one invocation measures:

  w=1 / w=4   — DistDeltaBigJoin epochs/sec + updates/sec on a 1- and
                4-worker CPU mesh, every epoch ALSO differentially checked
                against delta_oracle (throughput numbers are only kept if
                the signed outputs were bit-exact);
  local       — host-local DeltaBigJoin baseline on the same stream.

Per-epoch wall times land in the JSON so successive PRs can diff the warm
steady state (first epochs pay jit compilation of the per-plan programs).

Run via ``python -m benchmarks.run --only delta_stream`` (or directly).
"""
import json
import os
import subprocess
import sys

from benchmarks.common import row

OUT_PATH = os.path.join(os.path.dirname(__file__), "results",
                        "BENCH_delta_stream.json")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ARGS = ["--query", "triangle", "--nv", "80", "--ne", "800",
        "--batches", "10", "--batch-size", "64", "--batch", "512"]


def _run(extra):
    # the child fakes its workers as host CPU devices; pinning it to the
    # CPU keeps it off the chip this (parent) process may already hold
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "repro.core._delta_dist_check", *ARGS,
         *extra], capture_output=True, text=True, timeout=1800, env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"delta stream check failed: {out.stdout}\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    rec = {"bench": "delta_stream", "args": ARGS}
    for name, extra in (
            ("w1", ["--workers", "1"]),
            ("w4", ["--workers", "4"]),
            ("local", ["--workers", "1", "--local"])):
        r = _run(extra)
        assert r["all_exact"], f"{name}: differential check failed"
        warm = [e for e in r["epochs"][2:]] or r["epochs"]
        t = sum(e["elapsed_s"] for e in warm)
        ups = sum(e["updates"] for e in warm) / max(t, 1e-9)
        chg = sum(e["changes"] for e in warm) / max(t, 1e-9)
        rec[name] = {
            "workers": r["workers"], "mode": r["mode"],
            "all_exact": r["all_exact"],
            "shard_entries": r["shard_entries"],
            "warm_epochs_per_s": r["warm_epochs_per_s"],
            "warm_updates_per_s": round(ups, 1),
            "warm_changes_per_s": round(chg, 1),
            "epochs": r["epochs"],
        }
        row("delta_stream", name, t / max(len(warm), 1),
            f"{ups:.0f} upd/s exact={r['all_exact']}")
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump(rec, f, indent=2)
    row("delta_stream", "json", 0.0, OUT_PATH)


if __name__ == "__main__":
    main()
