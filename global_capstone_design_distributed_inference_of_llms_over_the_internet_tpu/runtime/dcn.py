"""Multi-host (DCN) layer: jax.distributed bring-up + cross-host meshes.

SURVEY.md §7.1 layer 7 — the reference's "over the Internet" story maps to
multi-pod/multi-host TPU: processes on different hosts form ONE JAX
multi-controller cluster, meshes span every host's devices, and XLA inserts
the cross-host (DCN) transfers wherever a sharding crosses a process
boundary. That replaces the reference's WAN data plane (libp2p RPC between
machines, ``src/rpc_transport.py``) for co-scheduled deployments; the framed
TCP swarm (runtime.net) remains the ELASTIC path where membership churns.

Division of labor:

  * control plane  — PlacementRegistry / RegistryServer (TTL liveness,
    elastic membership; scheduling.registry).
  * co-scheduled data plane — THIS module: `initialize()` forms the cluster,
    `global_mesh()` / `multihost_pipeline_mesh()` build device meshes whose
    axes span hosts, and the existing pjit/shard_map code (parallel.pipeline,
    parallel.tensor_parallel, parallel.ring_attention) runs on them
    UNCHANGED — multi-controller SPMD, every process executes the same
    program on its shard.
  * elastic data plane — framed TCP (runtime.net) between independent
    single-host processes.

CPU testing: a 2-process cluster over loopback with gloo collectives
(tests/test_dcn.py) exercises real cross-process psum/ppermute — the
in-process analogue the reference never had for its multi-machine setup
(SURVEY.md §4 "multi-node without a cluster: not simulated").
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Sequence, Tuple

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class DcnConfig:
    """One process's slot in the multi-host cluster.

    Mirrors the reference's bootstrap contract (every server needs the DHT
    initial peer, ``--dht_initial_peers``): every process needs the
    coordinator address and its own rank."""

    coordinator_address: str          # "host:port" of process 0's coordinator
    num_processes: int
    process_id: int
    # Tests / virtual clusters: force an n-device CPU host platform in THIS
    # process before the backend initializes (None = use real devices).
    cpu_devices_per_process: Optional[int] = None


def initialize(cfg: DcnConfig) -> None:
    """Form (or join) the cluster. Must run before the JAX backend
    initializes; afterwards `jax.devices()` is GLOBAL (all hosts) while
    `jax.local_devices()` is this process's slice."""
    if cfg.cpu_devices_per_process:
        from ..utils.platform import force_cpu_devices

        force_cpu_devices(cfg.cpu_devices_per_process)
    import jax

    jax.distributed.initialize(
        coordinator_address=cfg.coordinator_address,
        num_processes=cfg.num_processes,
        process_id=cfg.process_id,
    )
    logger.info("dcn: process %d/%d up, %d local / %d global devices",
                jax.process_index(), jax.process_count(),
                jax.local_device_count(), jax.device_count())


def shutdown() -> None:
    import jax

    jax.distributed.shutdown()


def global_mesh(axis_names: Sequence[str] = ("dp",),
                axis_sizes: Optional[Sequence[int]] = None):
    """A mesh over ALL processes' devices (process-major order, so slicing
    the FIRST axis across hosts keeps each host's shard local and pushes
    only that axis's collectives onto DCN — the layout §2.3 prescribes:
    collectives ride ICI within a host, DCN only across)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices())
    if axis_sizes is None:
        axis_sizes = (len(devs),) + (1,) * (len(axis_names) - 1)
    return Mesh(devs.reshape(tuple(axis_sizes)), tuple(axis_names))


def multihost_pipeline_mesh(num_stages: int, tp: int = 1):
    """("stage", "tp") mesh spanning hosts, stage-major: consecutive stages
    pack onto one host first, so only the stage boundaries that cross hosts
    pay DCN latency (the reference's per-hop WAN cost, paid at most
    (num_hosts - 1) times instead of (num_stages - 1))."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices())
    if num_stages * tp != len(devs):
        raise ValueError(
            f"mesh wants {num_stages}x{tp} devices, cluster has {len(devs)}")
    return Mesh(devs.reshape(num_stages, tp), ("stage", "tp"))


def sanity_check() -> Tuple[float, float]:
    """Cross-host collective smoke test: (psum of (process_id+1) over all
    devices, expected). Equal iff the cluster's data plane really spans
    processes — run on every host after initialize()."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = global_mesh(("dp",))
    n_local = jax.local_device_count()
    local = np.full((n_local, 1), float(jax.process_index() + 1), np.float32)
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local)

    @jax.jit
    def f(x):
        return jax.shard_map(lambda s: jax.lax.psum(s, "dp"),
                         mesh=mesh, in_specs=P("dp"), out_specs=P())(x)

    got = float(np.asarray(jax.device_get(f(arr).addressable_shards[0].data))[0, 0])
    # Expected sum from each device's OWNER process — exact on heterogeneous
    # clusters too (processes may contribute different device counts).
    want = float(sum(d.process_index + 1 for d in jax.devices()))
    return got, want


def ring_shift() -> bool:
    """Cross-host ppermute smoke test: shift one value around the global
    device ring (the pipeline's hop primitive, over DCN where the ring
    crosses processes). Returns True when every local shard received its
    predecessor's value."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = global_mesh(("dp",))
    n = jax.device_count()
    # Each shard's value = its GLOBAL row index, derived from the shard's own
    # index (not process_index * local_count, which assumes every process
    # contributes the same device count — false on heterogeneous clusters).
    arr = jax.make_array_from_callback(
        (n, 1), NamedSharding(mesh, P("dp")),
        lambda idx: np.asarray(
            [[float(i)] for i in range(idx[0].start or 0,
                                       idx[0].stop if idx[0].stop is not None
                                       else n)],
            np.float32))

    @jax.jit
    def f(x):
        perm = [(i, (i + 1) % n) for i in range(n)]
        return jax.shard_map(lambda s: jax.lax.ppermute(s, "dp", perm),
                         mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(x)

    out = f(arr)
    ok = True
    for shard in out.addressable_shards:
        got = float(np.asarray(jax.device_get(shard.data))[0, 0])
        want = float((shard.index[0].start - 1) % n)
        ok = ok and got == want
    return ok
