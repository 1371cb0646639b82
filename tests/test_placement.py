"""Placement rules: where the persistent compilation cache goes, and where a
mesh session's sharded index stacks live."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.core import compilestats

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str, *args: str, **env_over) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop(compilestats.ENV_VAR, None)
    env.update(env_over)
    out = subprocess.run([sys.executable, "-c", code, *args], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_CACHE_CHILD = """
import json, jax, repro
from repro.core import compilestats
print(json.dumps({"repro": compilestats.cache_dir(),
                  "jax": jax.config.jax_compilation_cache_dir}))
"""


def test_cache_dir_rule():
    assert compilestats.CHECKOUT_CACHE == str(REPO / ".jax_cache")
    assert compilestats.cache_dir_for({}) == compilestats.CHECKOUT_CACHE
    assert compilestats.cache_dir_for(
        {compilestats.ENV_VAR: "/srv/cache"}) == "/srv/cache"


def test_cache_dir_from_environment(tmp_path):
    where = str(tmp_path / "cache")
    got = _run(_CACHE_CHILD, **{compilestats.ENV_VAR: where})
    assert got == {"repro": where, "jax": where}


def test_cache_dir_defaults_to_checkout():
    got = _run(_CACHE_CHILD)
    assert got == {"repro": compilestats.CHECKOUT_CACHE,
                   "jax": compilestats.CHECKOUT_CACHE}


_SHARD_CHILD = """
import json, sys, jax, numpy as np
from jax.sharding import Mesh, NamedSharding
from repro.api import GraphSession
from repro.core.csr import AXIS
from repro.data.synthetic import EdgeUpdateStream, uniform_graph

order = json.loads(sys.argv[1])
mesh = Mesh(np.array(jax.devices())[order], (AXIS,))
edges = uniform_graph(64, 400, seed=3)
session = GraphSession(edges, local=False, mesh=mesh)
session.register("triangle")
local = GraphSession(edges, local=True)
local.register("triangle")
stream = EdgeUpdateStream(64, 32, insert_frac=0.75, seed=4)
deltas = []
for step in range(2):
    upd, w = stream.batch_at(step, live=edges)
    deltas.append([s.update(upd, w).deltas["triangle"].count_delta
                   for s in (session, local)])
arrays = []
for st in session.store._rels.values():
    arrays += [st.lb.key, st.lb.val, st.lb.n, st.lc_ins.key]
for reg in session.store.projections.values():
    arrays += [reg.d_base.key, reg.d_base.val, reg.d_base.n]
out = []
for a in arrays:
    s = a.sharding
    rows = sorted((sh.index[0].start or 0, sh.device.id)
                  for sh in a.addressable_shards)
    out.append({
        "named": isinstance(s, NamedSharding),
        "spec": [str(p) for p in s.spec] if isinstance(s, NamedSharding)
                else None,
        "row_devices": [d for _, d in rows],
        "shard_rows": sorted({sh.data.shape[0] for sh in a.addressable_shards}),
    })
print(json.dumps({"w": session.w, "axis": AXIS, "arrays": out,
                  "deltas": deltas}))
"""


@pytest.mark.parametrize("order", [[0, 1, 2, 3], [2, 0, 3, 1]],
                         ids=["device-order", "permuted"])
def test_w4_session_stacks_one_shard_per_device(order):
    """Worker row i of every stack lives on device i of the session's own
    mesh, in that mesh's device order, and the stream's deltas match the
    host-local engine."""
    got = _run(_SHARD_CHILD, json.dumps(order),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert got["w"] == 4
    assert got["arrays"]
    for a in got["arrays"]:
        assert a["named"] and a["spec"][0] == got["axis"], a
        assert a["row_devices"] == order, a
        assert a["shard_rows"] == [1], a  # one worker row per device
    for mesh_delta, local_delta in got["deltas"]:
        assert mesh_delta == local_delta
