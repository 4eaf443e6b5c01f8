"""Sharded-execution benchmark: comm-aware vs comm-free CSSE on a fake
8-device mesh.

For each workload/phase the comm-free (single-device) stage-2 winner and
the communication-aware one are searched, both are priced under the
mesh-aware model, and the *real* sharded ``execute`` of the comm-aware
winner is timed on an 8-fake-host-device mesh
(``--xla_force_host_platform_device_count=8``) against the single-device
einsum reference for a parity check.  Claims validated on every run:

* the comm-aware objective flips the winning contraction sequence on at
  least one workload/phase (ISSUE acceptance; the flip table is documented
  in ``docs/SHARDING.md``);
* the comm-aware winner is never worse than the comm-free winner under the
  mesh model (reranking can only help on its own objective);
* sharded execution matches the single-device reference (parity within
  f32 tolerance);
* the WG stash policy flips shared→indep once the dW all-reduce is priced.

Forcing host devices requires setting ``XLA_FLAGS`` before jax initialises,
so the measurement runs in a subprocess and reports rows as JSON — the
same isolation the 8-device tests use.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_WORKER = r"""
import json, time
import jax, jax.numpy as jnp
from repro.core import contraction, csse, factorizations as F
from repro.core import perf_model as pm
from repro.core import tensorized as tz
from repro.core.tnetwork import plan_from_tree
from repro.distributed import sharding

fact = F.tt((12, 8, 8), (8, 8, 12), 8)          # ATIS-TT (Table II)
tokens = 128
mesh = sharding.make_mesh((8, 1), ("data", "model"))
mspec = sharding.mesh_spec(mesh, {"b": ("data",)})

rows = []
phases = {
    "fp": fact.forward_network(batch_axes=(("b", tokens),)),
    "bp": tz._bp_network(fact, tokens),
    "wg0": tz._wg_network(fact, tokens, 0),
}
for phase, net in phases.items():
    free = csse.search(net, csse.SearchOptions(objective="latency",
                                               fused_chain=True))
    aware = csse.search(net, csse.SearchOptions(objective="latency",
                                                fused_chain=True,
                                                mesh=mspec))
    free_on_mesh = pm.evaluate(free.plan, fused_chain=True, mesh=mspec)

    arrays = [jax.random.normal(jax.random.key(i), net.node_shape(i),
                                jnp.float32) / 8
              for i in range(net.num_nodes)]
    ref = contraction.execute(aware.plan, arrays)
    fn = jax.jit(lambda ts: contraction.execute(aware.plan, ts, mesh=mesh))
    got = fn(arrays)
    parity = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    got.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        fn(arrays).block_until_ready()
    wall = (time.perf_counter() - t0) / 3

    rows.append({
        "name": f"sharded/ATIS-TT/{phase}",
        "wall_s": wall,
        "fusion_hit_rate": None,
        "flip": free.tree != aware.tree,
        "free_winner_mesh_latency_us": free_on_mesh.latency_s * 1e6,
        "aware_winner_mesh_latency_us": aware.cost.latency_s * 1e6,
        "collective_bytes": aware.cost.bytes_ici,
        "parity_rel_err": parity,
        "devices": jax.device_count(),
    })

# WG stash policy: the dW all-reduce flips shared -> indep on the mesh.
_, _, (kind_free, _, _) = tz._plans(
    fact, tokens, csse.SearchOptions(objective="latency", fused_chain=True))
_, _, (kind_aware, _, _) = tz._plans(
    fact, tokens, csse.SearchOptions(objective="latency", fused_chain=True,
                                     mesh=mspec))
dw_plan = csse.search(tz._dw_network(fact, tokens)).plan
rows.append({
    "name": "sharded/ATIS-TT/wg-policy",
    "wall_s": 0.0,
    "fusion_hit_rate": None,
    "policy_free": kind_free,
    "policy_aware": kind_aware,
    "dw_allreduce_bytes": pm.collective_cost(dw_plan, mspec,
                                             pm.TPU_V5E).bytes_ici,
    "devices": jax.device_count(),
})

# Pipeline bubble: 1F1B staged execution on this mesh, modeled (S-1)/(M+S-1)
# vs measured idle fraction (docs/DISTRIBUTED.md).  The report is the best
# warm step (per-stage jits compile on step 0); the drift also rides the
# telemetry drift channel, counted here so the record's presence is gated.
from repro import telemetry as tm
from repro.distributed import pipeline as pipe
tm.configure()
prep = pipe._demo_report(2, 4, 4)["report"]
ndrift = len([r for r in tm.drift_records()
              if r["name"] == "pipeline.bubble"])
rows.append({
    "name": "sharded/pipeline/bubble",
    "wall_s": prep["makespan_s"],
    "fusion_hit_rate": None,
    "num_stages": prep["num_stages"],
    "num_microbatches": prep["num_microbatches"],
    "modeled_bubble": prep["modeled_bubble"],
    "measured_bubble": prep["measured_bubble"],
    "bubble_drift": prep["drift"],
    "drift_records": ndrift,
    "devices": jax.device_count(),
})
print("ROWS=" + json.dumps(rows))
"""


def run(print_fn=print) -> list[dict]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # The forced devices are host devices: a CPU run, which must never
    # contend for a chip the parent process may hold.
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _WORKER],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("ROWS="))
    rows = json.loads(line[len("ROWS="):])
    for r in rows:
        if "flip" in r:
            print_fn(
                f"{r['name']}: flip={r['flip']} "
                f"free={r['free_winner_mesh_latency_us']:.2f}us "
                f"aware={r['aware_winner_mesh_latency_us']:.2f}us "
                f"ici={r['collective_bytes']}B "
                f"exec={r['wall_s']*1e3:.2f}ms "
                f"parity={r['parity_rel_err']:.1e}")
        elif "bubble_drift" in r:
            print_fn(
                f"{r['name']}: S={r['num_stages']} "
                f"M={r['num_microbatches']} "
                f"modeled={r['modeled_bubble']:.3f} "
                f"measured={r['measured_bubble']:.3f} "
                f"drift={r['bubble_drift']:.2f}x "
                f"({r['drift_records']} drift records)")
        else:
            print_fn(f"{r['name']}: {r['policy_free']} -> "
                     f"{r['policy_aware']} "
                     f"(dW all-reduce {r['dw_allreduce_bytes']}B)")
    return rows


def validate(rows) -> list[str]:
    failures: list[str] = []
    phase_rows = [r for r in rows if "flip" in r]
    if not any(r["flip"] for r in phase_rows):
        failures.append("comm-aware stage-2 flipped no winner on any phase")
    for r in phase_rows:
        if r["aware_winner_mesh_latency_us"] > \
                r["free_winner_mesh_latency_us"] * (1 + 1e-9):
            failures.append(
                f"{r['name']}: comm-aware winner worse than comm-free "
                "under the mesh model")
        if r["parity_rel_err"] > 1e-5:
            failures.append(f"{r['name']}: sharded parity "
                            f"{r['parity_rel_err']:.2e} > 1e-5")
        if r["devices"] != 8:
            failures.append(f"{r['name']}: ran on {r['devices']} devices, "
                            "expected 8")
    bubble = next((r for r in rows if "bubble_drift" in r), None)
    if bubble is None:
        failures.append("no pipeline bubble record")
    else:
        d = bubble["bubble_drift"]
        if max(d, 1.0 / max(d, 1e-9)) > 1.5:
            failures.append(
                f"pipeline bubble drift {d:.2f}x outside the 1.5x gate "
                f"(modeled {bubble['modeled_bubble']:.3f}, measured "
                f"{bubble['measured_bubble']:.3f})")
        if bubble["drift_records"] < 1:
            failures.append("pipeline step emitted no pipeline.bubble "
                            "telemetry drift record")
    policy = next(r for r in rows if r["name"].endswith("wg-policy"))
    if (policy["policy_free"], policy["policy_aware"]) != \
            ("shared", "indep"):
        failures.append(
            f"WG stash policy {policy['policy_free']} -> "
            f"{policy['policy_aware']}; expected shared -> indep once the "
            "dW all-reduce is priced")
    return failures


if __name__ == "__main__":
    rows = run()
    problems = validate(rows)
    for p in problems:
        print("FAIL:", p)
    raise SystemExit(1 if problems else 0)
