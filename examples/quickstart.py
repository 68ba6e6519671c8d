"""Quickstart: MultiWrite in 60 seconds.

1. The semantic: one MultiWrite == one copy per bottleneck link.
2. The paper's AllGather schedules + latency model.
3. A shard_map MultiWrite AllGather on whatever devices you have.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import latency_model as lm
from repro.core import schedules as sch
from repro.core.multiwrite import MultiWriteSimulator
from repro.core.topology import split_tp_full_mesh, two_server_cluster

# --- 1. the semantic ---------------------------------------------------------
print("== MultiWrite semantic ==")
topo = two_server_cluster()          # 2 servers x 8 NPUs, rail-optimized
sim = MultiWriteSimulator(topo)
token = np.arange(7168, dtype=np.uint8)

# unicast: 4 copies of the token cross NPU0's rail
for dst in (9, 10, 12, 15):
    sim.write(0, dst, "tok", token)
print(f"unicast   rail bytes: {sim.link_bytes[(0, 8)]:8d} "
      f"(redundant: {sim.redundant_bytes()[(0, 8)]})")

sim2 = MultiWriteSimulator(topo)
sim2.multiwrite(0, {d: "tok" for d in (9, 10, 12, 15)}, token)
print(f"multiwrite rail bytes: {sim2.link_bytes[(0, 8)]:8d} "
      f"(relay replicates at NPU8)")

# --- 2. the paper's AllGather schedules -------------------------------------
print("\n== AllGather on the split-TP full mesh (16 MB/rank) ==")
for scheme in ("baseline", "unicast_paired", "multiwrite_paired"):
    t = lm.allgather_latency(scheme, 16 * 2**20)
    print(f"  {scheme:20s}: {t*1e6:7.1f} us")
print(f"  -> MultiWrite cuts latency "
      f"{100 * (1 - lm.allgather_latency('multiwrite_paired', 16*2**20) / lm.allgather_latency('baseline', 16*2**20)):.0f}%"
      f"  (paper Fig 6: ~30%)")

# correctness: run the schedule through the packet simulator
topo8, domains = split_tp_full_mesh(8, tp=4)
sim3 = MultiWriteSimulator(topo8)
payloads = [np.random.default_rng(i).integers(0, 256, 4096, dtype=np.uint8)
            for i in range(8)]
sch.ALLGATHER_SCHEMES["multiwrite_paired"](sim3, domains, payloads)
sch.check_allgather(sim3, domains, payloads)
print("  schedule delivers every fragment bit-exactly: OK")

# --- 2b. the planner: scheme choice is dynamic (§5.2) ------------------------
print("\n== planner: baseline below the Fig 7 crossover, MultiWrite above ==")
from repro.core import planner as pl  # noqa: E402

for frag in (256 * 2**10, 16 * 2**20):
    d = pl.default_planner().choose("allgather", frag, topo8)
    print(f"  {frag/2**20:6.2f} MB -> {d.plan} "
          f"(predicted {d.predicted_s*1e6:.0f} us, "
          f"{d.speedup_pct:+.0f}% vs baseline)")

# --- 3. the JAX collective ----------------------------------------------------
print("\n== shard_map MultiWrite AllGather (local devices) ==")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import functools  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402
from repro.core import collectives as cl  # noqa: E402
from jax import shard_map  # noqa: E402

n = len(jax.devices())
if n >= 2 and n % 2 == 0:
    mesh = jax.make_mesh((n,), ("x",))
    x = jnp.arange(n * 8.0).reshape(n * 4, 2)
    fn = jax.jit(shard_map(
        functools.partial(cl.multiwrite_allgather, axis_name="x",
                          split=0.5),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
    ref = jax.jit(shard_map(
        functools.partial(cl.allgather_reference, axis_name="x"),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
    same = bool(jnp.array_equal(fn(x), ref(x)))
    print(f"  {n} devices: multiwrite_allgather == reference: {same}")
else:
    print(f"  ({n} device(s) — run tests/multidev for the 8-device check)")
print("\nDone.  See examples/train_100m.py for the end-to-end driver.")
