"""Multi-host bootstrap: `jax.distributed` from gang rendezvous info.

The reference's equivalent is env-var rendezvous for torchrun/NCCL
(SURVEY.md §5.8); here the control task (host 0 of the slice) is the
coordinator and XLA collectives ride ICI/DCN.
"""

import os


def initialize_from_current(timeout_ms=60_000):
    """Call inside a gang (@parallel/num_parallel) step to join the JAX
    multi-host process group. No-op for single-node gangs or when already
    initialized."""
    from ..current import current

    p = getattr(current, "parallel", None)
    if p is None or p.num_nodes <= 1:
        return False
    import jax

    from .. import telemetry

    # is_initialized, not process_count(): the latter starts the backend,
    # which must not exist before jax.distributed.initialize()
    if jax.distributed.is_initialized():
        return False
    # rendezvous cost is a first-class launch metric: a slow rank (or a
    # wedged coordinator) shows up as this timer in `tpuflow metrics`
    with telemetry.timer(
        "distributed.initialize",
        data={"num_nodes": p.num_nodes, "node_index": p.node_index},
    ):
        jax.distributed.initialize(
            coordinator_address="%s:%d" % (p.main_ip, p.coordinator_port),
            num_processes=p.num_nodes,
            process_id=p.node_index,
        )
    telemetry.event(
        "distributed.initialized",
        data={"process_index": jax.process_index(),
              "process_count": jax.process_count(),
              "local_devices": len(jax.local_devices()),
              "global_devices": len(jax.devices())})
    return True


def initialize_from_env():
    """TPU pod slice entry: on Cloud TPU VMs jax.distributed.initialize()
    discovers coordinator/world from the TPU metadata server."""
    import jax

    from .. import telemetry

    if jax.distributed.is_initialized():
        return False
    with telemetry.timer("distributed.initialize",
                         data={"source": "tpu_metadata"}):
        jax.distributed.initialize()
    return True


def process_info():
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }
