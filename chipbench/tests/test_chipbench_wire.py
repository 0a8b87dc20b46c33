"""The keystream roofline's byte count against the program's own accounting.

`record_wire_bytes` counts the coalesced wire's payload when a shuffle is
traced; `jobs/<kind>.py::wire_payload_bytes` computes it from the job's
shapes. They must agree for every cell's configuration, on one shard and
on four.
"""

import json

from chipbench.catalog import Catalog
from chipbench.tests.conftest import ROOT, run_python

N = 4096

CHECK = """
import json, sys
import numpy as np
import jax
from chipbench import harness
from chipbench.catalog import Catalog
from repro.compat import make_mesh
from repro.core.shuffle import record_wire_bytes
from repro.serve.service import RunnerCache, SecureJobService

cat = Catalog()
out = {}
for cfg_name in %(configs)r:
    cfg = cat.config(cfg_name)
    job = cat.job(cfg["job"])
    r = %(shards)d
    mesh = make_mesh((r,), ("data",), devices=jax.devices()[:r])
    data = job.make_data(cfg, %(n)d, 7, 0)
    with record_wire_bytes() as recs:
        with SecureJobService(mesh, secure=harness.secure_config(), cache=RunnerCache()) as svc:
            if cfg["job"] == "kmeans":
                svc.submit_kmeans(data["points"], cfg["k"], max_rounds=1).result()
            else:
                svc.submit_sort(data["keys"], max_rounds=1).result()
    out[cfg_name] = {"recorded": sorted({x["bytes"] for x in recs if not x["halted"]}),
                     "pad": sorted({x["pad_bytes"] for x in recs}),
                     "computed": job.wire_payload_bytes(cfg, %(n)d, r)}
print(json.dumps(out))
"""


def _check(shards: int):
    configs = [c["name"] for c in Catalog().index["configs"]]
    code = CHECK % {"configs": configs, "shards": shards, "n": N}
    out = json.loads(run_python(code, devices=shards).strip().splitlines()[-1])
    for name, r in out.items():
        assert r["recorded"] == [r["computed"]], (name, r)
        assert r["pad"] == [0], (name, r)


def test_wire_bytes_match_the_program_on_one_shard():
    _check(1)


def test_wire_bytes_match_the_program_on_four_shards():
    _check(4)
