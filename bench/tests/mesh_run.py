"""One tiny run of the 4-chip cell on 4 virtual CPU devices.

    python -m bench.tests.mesh_run [--fault exchange_left_out]

The device count must be set before JAX starts, hence a process of its
own.  Prints the run's checks as one JSON line."""
import argparse
import json
import os
import sys

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
    import jax

    from bench import cell
    from bench.tests.faults import FAULTS
    from bench.tests.helpers import tiny

    assert len(jax.devices()) == 4
    cfg, mix = tiny(config="rmat-triangle-4chip", mix="hub-window-b2048",
                    batch=64)
    fault = FAULTS[args.fault] if args.fault else None
    res = cell.run_cell(cfg, mix, seed=23, seconds=1.0, fault=fault)
    print(json.dumps({"checks": res["checks"], "device": res["device"],
                      "attempted": res["attempted"]}), flush=True)
    sys.exit(0)
