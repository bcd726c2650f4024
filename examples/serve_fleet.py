"""Fleet serving demo on 8 simulated devices.

    PYTHONPATH=src python examples/serve_fleet.py

Defaults ``--xla_force_host_platform_device_count=8`` (before jax import),
so a CPU-only host behaves like an 8-device host; the flag touches only the
CPU backend, so on a TPU host the fleet spans the real chips. The
FleetGraphEngine places
each registered graph's partition plan on one device (consistent-hash +
load-aware override), groups every flush by owning device, and launches the
per-device fused dispatches concurrently. A narrow giant graph takes the
block-sharded whole-mesh path instead — its partition blocks round-robin
across all devices and the per-device row slabs psum back together.
"""
import argparse
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.core.graph import gcn_normalize                    # noqa: E402
from repro.data.graphs import make_power_law_graph            # noqa: E402
from repro.serve.fleet import FleetGraphEngine                # noqa: E402
from repro.serve.graph_engine import (                        # noqa: E402
    GraphRequest, GraphServeEngine,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graphs", type=int, default=12)
    ap.add_argument("--nodes", type=int, default=300)
    ap.add_argument("--edges", type=int, default=2000)
    ap.add_argument("--feat", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()

    print(f"[serve_fleet] {len(jax.devices())} devices: {jax.devices()}")
    fleet = FleetGraphEngine(backend="blocked", max_graphs_per_batch=4)
    rng = np.random.default_rng(0)

    feats = {}
    for i in range(args.graphs):
        gid = f"g{i}"
        g = gcn_normalize(make_power_law_graph(
            args.nodes + 23 * i, args.edges + 77 * i, seed=i))
        fleet.register_graph(gid, g)
        feats[gid] = jnp.asarray(rng.normal(size=(g.n_cols, args.feat)),
                                 jnp.float32)
    cs = fleet.cache.stats()
    print(f"[serve_fleet] {args.graphs} plans placed over "
          f"{cs['devices']} devices; shard sizes={cs['shard_sizes']} "
          f"(overrides={cs['placement_overrides']})")

    # mixed recurring traffic: flushes group by owning device, devices fire
    # concurrently
    for rnd in range(args.rounds):
        reqs = [GraphRequest(gid, x) for gid, x in feats.items()]
        fleet.serve(reqs)
    st = fleet.stats()
    print(f"[serve_fleet] {st['requests_served']:.0f} requests in "
          f"{st['fleet_rounds']:.0f} fleet rounds "
          f"(graphs/round={st['fleet_graphs_per_round']:.1f}); "
          f"per-device dispatches={st['fleet_device_dispatches']} "
          f"occupancy={st['fleet_occupancy']:.2f}")

    # one giant narrow graph: block-sharded across the whole mesh
    # "giant" = past the 4096-row resident VMEM cap of one device
    big = gcn_normalize(make_power_law_graph(6000, 40000, seed=99))
    plan = fleet.register_graph("big", big)
    xb = jnp.asarray(rng.normal(size=(big.n_cols, args.feat)), jnp.float32)
    out = fleet.serve_one("big", xb)
    st = fleet.stats()
    print(f"[serve_fleet] giant graph: {plan.num_blocks} blocks "
          f"block-sharded -> per-device counts={st['fleet_block_counts']} "
          f"(balance={st['fleet_block_balance']:.3f}, 1.0 = perfect)")

    # cross-check against a single-device engine
    single = GraphServeEngine(backend="blocked")
    single.register_graph("big", big)
    ref = single.serve_one("big", xb)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"[serve_fleet] fleet vs single-device max|diff| = {err:.2e}")
    assert err < 1e-4
    fleet.close()
    single.close()
    print("[serve_fleet] OK")


if __name__ == "__main__":
    main()
