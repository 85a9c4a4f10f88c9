"""The paper's case study end-to-end: a TPC-DS-like sub-query executed on
the real JAX operator data plane AND planned/simulated on a 6-node cluster
under all four strategies.

    PYTHONPATH=src python examples/analytics_query.py
"""

import jax.numpy as jnp
import numpy as np

from repro.analytics import (
    QueryStrategy,
    Table,
    execute_query_jax,
    make_cluster,
    plan_query_tasks,
    reference_query_numpy,
    synth_table,
)
from repro.analytics.table import phantom
from repro.core.controllers import PrivateController


def main():
    # -- real data plane -------------------------------------------------------
    fact = synth_table("fact", 1 << 14, 1 << 12, seed=1)
    dim_cols = synth_table("dim", 1 << 10, 1 << 12, seed=2, unique_keys=True)
    dim = Table({**dim_cols.columns,
                 "cat": jnp.arange(1 << 10, dtype=jnp.int32) % 64})
    ref = reference_query_numpy(fact, dim)
    for method in ("hash", "merge"):
        got = np.asarray(execute_query_jax(fact, dim, method=method))
        err = np.abs(got - ref).max()
        print(f"[data plane] {method}_join groupby-sum max err vs numpy "
              f"oracle: {err:.2e}")

    # -- control plane: strategies on a 6-node cluster, 4 GB input ------------
    print(f"\n{'strategy':14s} {'completion':>11s} {'cost(slot-s)':>13s}")
    for strat in ("static_merge", "static_hash", "dynamic", "dynamic_fig6"):
        gc, sim = make_cluster(6)
        pc = PrivateController("query", gc, priority=10)
        f = phantom("A", int(3.6 * 2 ** 30), range(6))
        d = phantom("B", int(0.2 * 2 ** 30), range(2))
        plan_query_tasks(sim, pc, f, d, QueryStrategy(strat))
        out = sim.run()
        print(f"{strat:14s} {out['completion']['query']:10.2f}s "
              f"{out['cost_slot_seconds']['query']:13.1f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
