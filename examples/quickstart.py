"""Quickstart: the paper's scheduling framework in 30 lines.

Builds a tile-Cholesky task DAG, schedules it with HEFT and DADA on the
paper's 12-CPU + 8-GPU machine model, executes the DADA schedule with real
JAX tile kernels, and verifies the numerics.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.paper_machine import paper_machine
from repro.core import run_simulation
from repro.linalg import tiles as T
from repro.linalg.cholesky import cholesky_graph
from repro.linalg.execute import execute_schedule
from repro.sched import resolve

N, TILE = 1024, 128
NT = N // TILE

machine = paper_machine(n_gpus=4)
graph = cholesky_graph(NT, TILE)
print(f"Cholesky {N}x{N}: {len(graph)} tasks, {graph.n_edges} edges")

# policies come from the registry: bare names or query-string specs
for spec in ["heft", "dada?alpha=0.5&use_cp=1", "ws", "locality", "random"]:
    strat = resolve(spec)
    res = run_simulation(cholesky_graph(NT, TILE, with_fns=False), machine, strat, seed=0)
    print(f"  {res.strategy:12s} {res.gflops:7.1f} GFLOPS  "
          f"{res.gbytes*1e3:7.1f} MB moved  {res.n_steals} steals")

# execute the affinity schedule for real and check the factorization
a = T.random_spd(N, seed=0, dtype=jnp.float32)
res = run_simulation(
    cholesky_graph(NT, TILE, with_fns=False), machine,
    resolve("dada?alpha=0.5"), seed=0,
)
store = execute_schedule(graph, T.split_tiles(a, TILE), res)
L = jnp.tril(T.join_tiles(store, NT, TILE))
with jax.default_matmul_precision("highest"):  # an f32 check on any device
    err = float(jnp.abs(L @ L.T - a).max() / jnp.abs(a).max())
# f32 Cholesky: |LL^T - A| <= gamma_{N+1} |L||L^T| <= gamma_{N+1} max|A|
# (Higham, Thm 10.3), plus as much again twice for the check's own rounding
u = 2.0 ** -24
bound = 3 * (N + 1) * u / (1 - (N + 1) * u)
print(f"DADA schedule executed on JAX: ||LL^T - A|| rel err = {err:.2e} "
      f"(f32 bound {bound:.2e})")
assert err <= bound
print("OK")
