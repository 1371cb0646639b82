"""Fig 5 / Table 4: scaling workers x graph fractions.

Each configuration runs the *distributed* engine in a subprocess with w
forced host devices (1 physical core underneath, so wall-clock does not
speed up — the Fig-5 quantities that transfer to this container are the
per-worker index size, per-worker served load (balance), and round counts,
all of which must scale ~1/w; wall time is reported for completeness)."""
import json
import os
import subprocess
import sys

from benchmarks.common import row

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_cfg(workers, ne, nv, query="triangle", batch=1024):
    # the child fakes its workers as host CPU devices; pinning it to the
    # CPU keeps it off the chip this (parent) process may already hold
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "repro.core._dist_check",
         "--workers", str(workers), "--query", query, "--ne", str(ne),
         "--nv", str(nv), "--batch", str(batch), "--skew",
         "--route-capacity", str(max(batch // max(workers, 1), 16) * 4)],
        capture_output=True, text=True, timeout=1200, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    for frac, ne in [("1/4", 2500), ("1/2", 5000), ("1/1", 10000)]:
        for w in (1, 2, 4, 8):
            r = run_cfg(w, ne, nv=400)
            mean = max(r["mean_load"], 1.0)
            row("fig5_scaling", f"edges{frac.replace('/', 'of')}_w{w}",
                r["warm_s"],
                f"count={r['dist_count']};rounds={r['steps']};"
                f"max_load={r['max_load']};"
                f"load_imbalance={r['max_load'] / mean:.2f};"
                f"edges_per_worker={r['edges'] // w}")


if __name__ == "__main__":
    main()
