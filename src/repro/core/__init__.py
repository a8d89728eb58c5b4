"""Core of the framework — FastFlow's layered streaming-network model,
adapted from shared-memory multicores to TPU pods, unified behind one
composable *building blocks* graph API and one staged graph compiler.

Layer 1-2 (``core.queues``, ``core.shm``, ``core.net``): lock-free SPSC
ring buffers, composed into SPMC / MPSC / MPMC networks — the channels every
host skeleton runs over.  ``core.queues`` is the thread-tier instance;
``core.shm`` lays the same fixed-slot ring out in
``multiprocessing.shared_memory`` so the ring crosses OS processes —
FastFlow's actual multicore claim — in three lane tiers: the bounded SPSC
ring (raw-numpy slab fast path, pickled-bytes fallback, back-pressure when
full), the *uSPSC* unbounded tier of the 2009 FastFlow TR
(``ShmUSPSCQueue``: a linked chain of ring segments grown on overflow and
retired on drain, so the producer never blocks), and the ``ShmArena`` slab
for ndarrays larger than a ring slot (shipped as arena offsets, never
pickled).  Every lane moves items *vectored* — ``push_many``/``pop_many``
pay one atomic index write and one spin per batch, with small non-array
items coalescing into single batch slots — and ``compile(transport=...)``
(a ``TransportConfig``) tunes ring depths, slot/arena sizes,
bounded-vs-uSPSC, and the batch flush policy per compile.  ``core.net``
speaks the same slot protocol over TCP (length-prefixed frames, u64 seqs,
EOS/ERR control, plus credit-window back-pressure and heartbeats) so the
lane crosses the *host* boundary — the distributed tier.

Layer 3 (``core.node``, ``core.skeletons``): the paper-faithful host
runtime — ``ff_node`` (``svc``/``svc_init``/``svc_end``), ``Pipeline``,
``Farm`` (emitter / collector / load balancers / on-demand / autoscaling),
``FFMap``, ``wrap_around`` feedback, and the accelerator mode
(``run_then_freeze`` / ``offload`` / ``load_result`` / ``FF_EOS`` / ``wait``).

Building blocks (``core.graph``): the declarative front door.  Programs are
written as an ``FFGraph`` of composable blocks — ``seq``, ``pipeline``,
``farm`` (including ``n="auto"`` and ``autoscale=True`` widths), ``ffmap``,
``all_to_all`` (FastFlow 3's ``ff_a2a``), plus ``wrap_around`` feedback.

The staged compiler (``core.compiler``): ``FFGraph.compile(plan)`` runs four
explicit stages —

1. **normalize**: the ``optimize()`` normal-form rewrites (pipeline
   flattening, collector-emitter collapse, farm/pipeline fusion);
2. **annotate**: a ``CostEstimate`` per node from the paper's Sec. 13
   algebra in ``core.perf_model`` (declared ``ff_cost``/``ff_flops``,
   explicit ``costs=``, or timing the node on a ``sample`` item), plus a
   GIL-sensitivity signal: set ``fn.ff_releases_gil = True`` on workers
   whose hot loop drops the GIL (I/O, large BLAS calls, jitted device
   steps) or ``False`` on ones that hold it (pure-Python / small-array
   numpy); undeclared workers are probed by timing the node solo vs. under
   two concurrent threads when a ``sample`` is available;
3. **place**: a ``Placement`` per top-level stage across the *four-tier*
   host side plus the mesh — host *thread*, host *process* (a GIL-bound
   farm or ``all_to_all`` gains true parallelism worth more than the
   shared-memory hop), host *remote* (``host_remote``: the farm's workers
   live in ``python -m repro.launch.worker`` pools on other hosts, reached
   over the network lanes of ``core.net`` and unlocked by
   ``compile(remote_workers=["host:port", ...])`` — chosen when
   parallelism over the network hop beats both on-box tiers, or forced
   with ``mode="remote"``), or *device* — consuming the constants
   ``perf_model.calibrate()`` measures at startup (host peak FLOP/s,
   thread-queue hop, process-lane hop per item AND amortized over a
   vectored batch — the batched hop is what the process tier is actually
   charged — slab-arena bandwidth, loopback network hop, device
   dispatch; cached on disk,
   ``REPRO_FF_CACHE``/``XDG_CACHE_HOME``-relocatable for hermetic CI, and
   degrading to in-memory constants with a warning when the cache dir is
   unwritable) instead of baked-in defaults; farm width from
   ``choose_farm_width``, a2a service time from ``a2a_service_time``; all
   overridable per node;
4. **emit**: ``HostRunner`` (threads over SPSC queues), ``ProcessRunner``
   (process-placed farms run OS-process workers over the shared-memory
   rings of ``core.shm``, bridged into the thread network by
   ``core.process.ProcessFarmNode`` — order-preserving, crash-surfacing,
   optionally autoscaling its active worker set from shm lane depth;
   process-placed ``all_to_all`` stages run left/right worker processes
   over the ``core.shm.ShmMPMCGrid`` lane grid via
   ``core.process.ProcessA2ANode``, the router shipped to the left
   children and sequence numbers riding the slot headers),
   ``RemoteRunner`` (remote-placed farms run ``core.net.RemoteFarmNode``
   boundary nodes: per-worker TCP lanes with a bounded credit window,
   sequence-ordered collection, heartbeat crash surfacing, and
   ``set_active``-driven *cluster autoscaling* — AutoscaleLB and the
   runtime Supervisor grow/shrink the active remote worker set from
   observed lane depth), ``DeviceRunner`` (the mesh via ``core.device``),
   or the *hybrid* runner — host stages over SPSC queues feeding device
   segments through device-put boundary nodes.  Thread -> process ->
   remote -> device programs compose in one graph; every block has a
   backend on each eligible tier.

``emit`` covers every block on both targets: farms are ``shard_map`` over
the data axis, ``all_to_all`` lowers to ONE fused Pallas dispatch/combine
kernel (``kernels.a2a_fused`` via ``core.device.a2a_dispatch``: route,
capacity position, expert compute, and stream-order combine in a single
``pallas_call``, per-expert lane cursors in VMEM scratch, ``expert_capacity``
sizing the lanes, and the kernel itself sharded over the mesh in the
lossless case), and ``wrap_around`` lowers through
``core.device.feedback_scan`` when ``feedback_steps=K`` bounds the loop or
through ``core.device.feedback_while`` (a masked, vmap-safe
``lax.while_loop``) when ``feedback_cond=`` gives a data-dependent exit
predicate.  All compile knobs consolidate into one
:class:`~repro.core.compiler.CompileConfig` dataclass —
``graph.compile(config=CompileConfig(...))`` is the supported surface, and
the legacy flat kwargs remain as a one-``DeprecationWarning`` shim.
``lower(plan)`` stays as a thin compat wrapper forcing all-host
(``plan=None``) or all-device placement.  The data pipeline, the serving
engine, and the launch entry points are all expressed as FFGraph programs
compiled through this pipeline.

Device-segment fusion (``core.fuse``): between ``place`` and ``emit``,
every maximal run of *adjacent* device-placed stages is greedily merged
into one ``FusedSegment`` and lowered as ONE compiled program — a single
``jax.jit``, one device put in, one host copy out per run, whether the run
is a pipeline of pure stages, vmapped farm/``ffmap`` bodies, a fused-a2a
hop, or a ``feedback_scan`` tail.  This is the paper's layered lesson
applied to the device tier: composition must collapse into cheap
communication, so N composed stages cost one dispatch, not N host
round-trips.  ``compile(fuse=False)`` restores the one-program-per-stage
emit (per-stage observability, A/B comparisons).  Jitted segments are
cached across ``compile()`` calls keyed by fused-stage identity, so the
adaptive Supervisor's re-place path reuses traced programs instead of
retracing; ``place`` amortizes the calibrated ``device_dispatch_s`` over
each candidate run (plus the measured ``fused_segment_s`` marginal), which
is what lets fused device placement win at much smaller stage grain.

The overlapped device boundary: fusion made each host<->device hop cost one
dispatch; overlap makes those dispatches *asynchronous and software-
pipelined*.  Both boundary emits — the hybrid runner's
``_DeviceStageNode`` and ``DeviceRunner``'s microbatched whole-graph path —
keep a depth-K in-flight window of microbatches riding JAX's async
dispatch: microbatch i+1 stacks and dispatches, and i-1's device->host copy
completes (``copy_to_host_async``), while i computes; nothing calls
``block_until_ready`` until the window is full, and FIFO retirement
preserves exact input order.  Three ``CompileConfig`` knobs govern it —
``overlap`` (default True), ``microbatch`` (the boundary's stacking unit,
``device_batch``'s modern name), and ``inflight`` (the window depth,
default 2).  ``overlap=False`` (or ``inflight=1``) restores the strictly
synchronous put -> compute -> copy-out boundary and is byte-identical —
the same jitted programs see the same stacked inputs; only the
synchronization point moves.  ``place`` costs a fused device run at
``max(transfer, compute)`` through the calibrated h2d/d2h bandwidths and
overlap efficiency (calib cache v5), boundary nodes publish
submit/drain/stall stats through a ``boundary_tunable``
``DeviceBoundaryHandle``, and the runtime Supervisor retunes the window
depth live from the observed stall fraction.  Feedback (``wrap_around``)
graphs force the sync boundary — a window holding results back would
starve the loop.

The adaptive runtime (``core.runtime``) closes the stats -> placement loop
*at runtime*: ``compile(adaptive=True)`` lowers eligible farms to
reconfigurable ``AdaptiveFarmNode`` boundary stages (sequence-ordered on
both host tiers), every runner exposes a uniform per-stage ``StageHandle``
surface (stats + resize/migrate), and a ``Supervisor`` thread samples it —
growing/shrinking active worker sets from observed lane depth (the
AutoscaleLB policy generalized to any adaptive farm on either tier),
migrating a farm thread <-> process mid-stream when the observed
GIL-serialized service time crosses the other tier's estimate (drain to a
quiescent EOS-style barrier, hot-swap the engine behind the stage's
boundary queues, resume — order and error semantics unchanged), and feeding
measured service times, GIL signals, and hop costs back into the
calibration cache via ``perf_model.observe`` so the *next* ``compile()``'s
``place()`` decisions improve.  Calibration is no longer a startup-only
event.  With ``adaptive=False`` (the default) nothing here runs.

Device side: ``core.plan`` maps logical tensor axes onto mesh axes,
``core.device`` holds the mesh lowerings, ``core.accelerator`` treats a
compiled SPMD step as an offload target, and ``core.perf_model`` extends the
paper's Sec. 13 cost model with a TPU roofline.
"""

from .node import EOS, GO_ON, FFNode, FnNode
from .queues import MPMCQueue, MPSCQueue, QueueClosed, SPMCQueue, SPSCQueue
from .skeletons import (AutoscaleLB, BroadcastLB, Farm, FF_EOS, FFMap,
                        LoadBalancer, OnDemandLB, Pipeline, RoundRobinLB,
                        Skeleton, ThreadFarmNode)
from .shm import (BatchedLaneWriter, ShmArena, ShmMPMCGrid, ShmMPSCQueue,
                  ShmSPMCQueue, ShmSPSCQueue, ShmUSPSCQueue, TransportConfig,
                  as_transport)
from .graph import (A2ASkeleton, Deliver, FFGraph, GraphError, Runner,
                    StageHandle, all_to_all, farm, ffmap, pipeline, seq)
from .graph import HostRunner, DeviceRunner
from .process import ProcessA2ANode, ProcessFarmNode, WorkerCrashed
from .net import (NetLane, RemoteFarmNode, RemoteStageHandle,
                  spawn_loopback_pool, worker_main)
from .compiler import (CompileConfig, CostEstimate, HybridRunner, Placement,
                       ProcessRunner, RemoteRunner, annotate, compile_graph,
                       emit, place)
from .runtime import (AdaptiveFarmNode, AdaptiveStageHandle,
                      ReplacementEvent, SLOPolicy, Supervisor)
from .accelerator import JaxAccelerator
from .plan import DEFAULT_RULES, ShardingPlan, single_device_plan
from . import device, perf_model

__all__ = [
    "EOS", "GO_ON", "FF_EOS", "FFNode", "FnNode",
    "SPSCQueue", "SPMCQueue", "MPSCQueue", "MPMCQueue", "QueueClosed",
    "ShmSPSCQueue", "ShmSPMCQueue", "ShmMPSCQueue", "ShmMPMCGrid",
    "ShmUSPSCQueue", "ShmArena", "TransportConfig", "BatchedLaneWriter",
    "as_transport",
    "Pipeline", "Farm", "FFMap", "Skeleton", "ThreadFarmNode",
    "LoadBalancer", "RoundRobinLB", "OnDemandLB", "BroadcastLB",
    "AutoscaleLB",
    "FFGraph", "GraphError", "Deliver", "Runner", "StageHandle",
    "HostRunner", "DeviceRunner", "HybridRunner", "ProcessRunner",
    "A2ASkeleton", "ProcessFarmNode", "ProcessA2ANode", "WorkerCrashed",
    "NetLane", "RemoteFarmNode", "RemoteStageHandle", "RemoteRunner",
    "spawn_loopback_pool", "worker_main",
    "AdaptiveFarmNode", "AdaptiveStageHandle", "ReplacementEvent",
    "SLOPolicy", "Supervisor",
    "seq", "pipeline", "farm", "ffmap", "all_to_all",
    "CompileConfig", "CostEstimate", "Placement", "annotate", "place",
    "emit", "compile_graph",
    "JaxAccelerator", "ShardingPlan", "single_device_plan", "DEFAULT_RULES",
    "device", "perf_model",
]
