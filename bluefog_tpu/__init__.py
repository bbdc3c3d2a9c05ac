"""bluefog_tpu: decentralized distributed training, TPU-native.

A from-scratch JAX/XLA implementation of BlueFog's capability surface
(reference: github Bluefog-Lib/bluefog, mounted at /root/reference):
decentralized data-parallel optimization over static and dynamic virtual
graph topologies, one-sided gossip windows, hierarchical averaging, classic
collectives, optimizer wrappers, a launcher, a timeline profiler.

Usage mirrors ``import bluefog.torch as bf`` (reference: torch/__init__.py:35-62):

    import bluefog_tpu as bf
    bf.init(bf.topology_util.ExponentialTwoGraph)
    x = ...  # rank-stacked array [bf.size(), ...], slice r on device r
    y = bf.neighbor_allreduce(x)

Ranks are devices of a ``jax.sharding.Mesh``; every op runs as one SPMD
program with ``ppermute``/``psum`` collectives over ICI.
"""

import time as _time

_stamps = [("begin", _time.perf_counter())]  # (import group that ended, clock)

from . import topology as topology_util
from .version import __version__

# lifecycle + introspection
from .runtime.state import (
    init,
    shutdown,
    size,
    local_size,
    local_rank,
    rank,
    num_machines,
    machine_size,
    is_homogeneous,
    mesh,
    machine_mesh,
    set_topology,
    load_topology,
    is_topo_weighted,
    in_neighbor_ranks,
    out_neighbor_ranks,
    set_skip_negotiate_stage,
    get_skip_negotiate_stage,
    unified_mpi_window_model_supported,
    mpi_threads_supported,
    nccl_built,
)

# handles
from .runtime.handles import poll, synchronize, wait

# failure detection / coordinated shutdown / fault tolerance / elastic
# membership (multi-controller; see docs/fault_tolerance.md)
from .runtime.heartbeat import (
    dead_controllers,
    dead_ranks,
    membership_epoch,
    shutdown_requested,
    suspect_controllers,
)
from .runtime.native import (PeerLostError, QuorumLostError,
                             StaleIncarnationError)

# timeline
from .runtime.timeline import (
    start_timeline,
    stop_timeline,
    timeline_start_activity,
    timeline_end_activity,
    timeline_context,
)

# telemetry plane: metrics registry + cluster health (docs/metrics.md)
from .runtime import metrics
from .runtime.metrics import cluster_health

# flight recorder: always-on black box + postmortem dumps + step-time
# attribution (docs/flight_recorder.md)
from .runtime import flight
from .runtime.flight import step_report

_stamps.append(("runtime", _time.perf_counter()))


def flight_dump(reason: str = "explicit", path=None):
    """Dump the flight recorder NOW (ring tail + native transport events +
    metrics snapshot) to ``bf_flight_<rank>.json`` under
    ``BLUEFOG_FLIGHT_DIR`` and, when a control plane is attached, publish
    the packed tail under ``bf.flight.<rank>`` for ``bfrun --dump``.
    Returns the local dump path (docs/flight_recorder.md)."""
    return flight.dump(reason=reason, path=path, force=True)

# ops
from .ops import (
    allgather,
    allgather_nonblocking,
    allgather_v,
    allgather_v_nonblocking,
    allreduce,
    allreduce_nonblocking,
    allreduce_,
    allreduce_nonblocking_,
    barrier,
    broadcast,
    broadcast_nonblocking,
    broadcast_,
    broadcast_nonblocking_,
    pair_gossip,
    pair_gossip_nonblocking,
    hierarchical_neighbor_allreduce,
    hierarchical_neighbor_allreduce_nonblocking,
    neighbor_allgather,
    neighbor_allgather_nonblocking,
    neighbor_allreduce,
    neighbor_allreduce_nonblocking,
    CombinePlan,
    apply_plan,
    rank_sharding,
    shard_rank_stacked,
    get_win_version,
    turn_off_win_ops_with_associated_p,
    turn_on_win_ops_with_associated_p,
    win_accumulate,
    win_accumulate_nonblocking,
    win_associated_p,
    win_associated_p_all,
    win_create,
    win_fence,
    win_free,
    win_get,
    win_get_nonblocking,
    win_lock,
    win_mutex,
    win_poll,
    win_put,
    win_put_nonblocking,
    win_update,
    win_update_then_collect,
    win_wait,
)

_stamps.append(("ops", _time.perf_counter()))

# optimizer wrappers (reference: torch/optimizers.py)
from .optimizers import (
    TrainState,
    replicate,
    unreplicate,
    DistributedGradientAllreduceOptimizer,
    DistributedAllreduceOptimizer,
    DistributedNeighborAllreduceOptimizer,
    DistributedHierarchicalNeighborAllreduceOptimizer,
    DistributedShardedAllreduceOptimizer,
    DistributedWinPutOptimizer,
    DistributedPullGetOptimizer,
    DistributedPushSumOptimizer,
    step_programs,
)

_stamps.append(("optimizers", _time.perf_counter()))

# parameter/optimizer-state sync utilities (reference: torch/utility.py)
from .utils import (
    broadcast_parameters,
    allreduce_parameters,
    broadcast_optimizer_state,
    resnet_from_torch,
    vgg_from_torch,
)

_stamps.append(("utils", _time.perf_counter()))

from . import checkpoint

_stamps.append(("checkpoint", _time.perf_counter()))

from . import models

_stamps.append(("models", _time.perf_counter()))

from . import parallel

_stamps.append(("parallel", _time.perf_counter()))

# serving plane: versioned snapshot distribution + batched read-only
# inference over the control-plane wire (docs/serving.md). bf.serve_client()
# attaches from inside a job; standalone serving processes import
# ``bluefog_tpu.serving`` through the lean bootstrap instead (no jax).
from .serving import RequestShed, ServeClient, serve_client

_stamps.append(("serving", _time.perf_counter()))

# Seconds ``import bluefog_tpu`` took, by import group and in ``"total"``
# (a module imported before by someone else costs its group nothing). Kept
# here because bf.init() zeroes the registry; it writes them after that reset
# as the gauges ``import.total_sec`` and ``import.<group>_sec``.
IMPORT_SECONDS = {group: t - before for (_, before), (group, t)
                  in zip(_stamps, _stamps[1:])}
IMPORT_SECONDS["total"] = _stamps[-1][1] - _stamps[0][1]
del _stamps
