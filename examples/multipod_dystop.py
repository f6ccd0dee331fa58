"""Pods-as-workers: DySTop's pull-aggregate running as a shard_map collective
over the `pod` mesh axis (the production mapping described in DESIGN.md §3).

Runs on CPU by forcing 8 host devices -> a (4, 2) (pod, data) mini-mesh: four
"pods", each holding one DFL replica (param leaves have a leading pod axis
sharded over `pod`).  The coordinator (WAA) activates pods host-side; the
staleness-weighted mixing matrix is applied with one all_gather over `pod`
per leaf — the PULL+aggregate of paper Alg. 1 with ICI as the transport.

    PYTHONPATH=src python examples/multipod_dystop.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.aggregation import mixing_matrix
from repro.core.protocol import dystop_pod_mix
from repro.core.staleness import StalenessState
from repro.core.waa import worker_activation
from repro.dfl import worker as WK
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh


def main():
    enable_compile_cache()
    n_pods = 4
    mesh = make_mesh((n_pods, 2), ("pod", "data"))

    # four pod replicas, intentionally divergent, sharded over the pod axis
    keys = jax.random.split(jax.random.PRNGKey(0), n_pods)
    stacked = jax.vmap(lambda k: WK.init_mlp(k, 16, 32, 4))(keys)
    stacked = jax.tree.map(
        lambda l: jax.device_put(
            l, NamedSharding(mesh, P("pod", *[None] * (l.ndim - 1)))), stacked)

    st = StalenessState.create(n_pods, tau_bound=2)
    rng = np.random.default_rng(0)
    mix = jax.jit(lambda s, w: dystop_pod_mix(s, w, mesh))

    for t in range(1, 6):
        # control plane (host): WAA over simulated pod round costs
        cost = rng.uniform(1.0, 3.0, n_pods)
        active, _ = worker_activation(st, cost, V=5.0)
        links = np.zeros((n_pods, n_pods), bool)
        for i in np.flatnonzero(active):      # each active pod pulls all peers
            links[i] = True
            links[i, i] = False
        W = mixing_matrix(active, links, np.ones(n_pods))

        # data plane: all_gather over `pod` + per-pod weighted mix
        stacked = mix(stacked, jnp.asarray(W))
        st.advance(active)

        spread = float(jnp.std(stacked["w1"].astype(jnp.float32), axis=0).mean())
        print(f"round {t}: active={np.flatnonzero(active).tolist()} "
              f"tau={st.tau.tolist()} replica-spread={spread:.4f}")

    print("replica spread shrinks as activated pods pull+aggregate — "
          "DySTop over the pod axis works.")


if __name__ == "__main__":
    main()
