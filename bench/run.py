#!/usr/bin/env python3
"""The benchmark's one command: run one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload tri1.hub-b2048 --seed 7 --seconds 51 \\
        --trace 0

Everything a cell needs is found by name: its configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` (read by the one generator,
``bench/traffic.py``), and each per-layer metric's reader in
``bench/layers/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number the reference comparison
counted, with its limit.  The same checks are the last lines on standard
error.  Without a TPU, or with fewer chips than the cell asks for, the
command exits non-zero and prints no result.

``--control`` runs the correctness control instead of the program (see
``bench/control.py``); the benchmark's own runs never pass it.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def read_layer(name: str, obs) -> float | None:
    """Run the reader ``bench/layers/<name>.py``; None if it found
    nothing to read."""
    path = os.path.join(HERE, "layers", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


def print_checks(checks: dict) -> None:
    for name, value in checks.items():
        print(f"check {name}: {value} (limit 0)", file=sys.stderr,
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the correctness control, not the program")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    # the compile cache lives at a fixed path inside the checkout; the
    # program takes the directory this variable names
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    # the TPU runtime's own logs would go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    bench = load_benchmark()
    cell = find(bench["workloads"], args.workload, "workload")
    from bench import cell as cellmod
    from bench import traffic
    cfg = cellmod.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])

    if args.control:
        from bench import control
        rec = control.run_control(cfg, mix, args.seed)
        print_checks(rec["checks"])
        print(json.dumps(rec), flush=True)
        return 0

    try:
        import repro  # noqa: F401  (turns the compile cache on first)
    except ImportError as e:
        print(f"run.py: the program is not here ({e})", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"run.py: the cell needs {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    res = cellmod.run_cell(cfg, mix, args.seed, args.seconds,
                           trace=bool(args.trace), t_start=T_PROCESS)
    if not window_was_warm(res["window"]):
        return 3
    return emit(bench, cell, res, bool(args.trace))


def window_was_warm(window: dict) -> bool:
    """A program built inside the window means the warm-up no longer
    covers the window's churn (a faster program reaches the next rung of
    the committed regions sooner): the run measured compiles, not the
    monitor, and gives no result."""
    if window["compiles"] == 0 and window["backend_compiles"] == 0:
        return True
    print(f"run.py: {window['compiles']} compile events and "
          f"{window['backend_compiles']} programs built inside the window; "
          f"the mix's warmup_batches no longer covers the window",
          file=sys.stderr, flush=True)
    return False


def emit(bench: dict, cell: dict, res: dict, trace: bool) -> int:
    """Print the result line for one finished run."""
    name = cell["name"]
    metrics = {}
    out = {"correct": all(v == 0 for v in res["checks"].values()),
           "attempted": res["attempted"], "failed": res["failed"]}
    device = dict(res["device"])
    if not trace:
        values = dict(res["e2e"], setup_s=res["setup_s"],
                      peak_hbm_mb=device["memory_peak_bytes"] / 1e6)
        for m in bench["end_to_end"]:
            if name in m.get("workloads", [name]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        from bench import trace as tracemod
        obs = tracemod.Observed.load(res, cell)
        for m in bench["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            value = read_layer(m["name"], obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = obs.busy_s
        device["window_s"] = obs.window_s
        out["breakdown"] = obs.breakdown()
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {k: {"value": v, "limit": 0}
                     for k, v in res["checks"].items()}
    print_checks(res["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
