"""repro.serving — TWO engines behind one gateway front-end, scaled out
by a fleet tier.

The serving stack batches both of the repo's engines through the same
queue/batcher machinery (``GatewayBase``: intake, serve thread, drain,
stats):

* FLOW — ``FlowSampler`` / ``AnytimeFlowSampler`` (the paper's product:
  m-forward BNS sampling, budget-routed multi-NFE serving from one
  artifact), fronted by ``Gateway`` (budget-coalescing padded flush
  batches) and ``ContinuousGateway`` (requests join in-flight anytime
  trajectories at exit boundaries).
* DECODE — ``DecodeEngine`` (autoregressive decode with KV-cache /
  recurrent state, jit'd multi-token ``greedy`` plus the slot-masked
  ``step_slots``/``prefill_slots`` API; ``page_size > 0`` swaps the dense
  per-slot cache for a shared paged pool + block tables, optionally through
  the Pallas paged-attention kernel; per-request ``SamplingParams`` add
  temperature/top-k/top-p beside greedy), fronted by ``DecodeGateway``
  (continuous batching over per-sequence state slots: finished sequences
  free their row AND their KV pages, queued sequences are admitted at the
  next engine step with chunked batched prefill, per-slot stop conditions,
  cancelled futures released at the next pump).

Five layers, bottom up — each consumes the one below and widens the
concurrency it can absorb:

1. **sampler** (``engine``) — one jit'd dispatch: a padded batch in,
   samples/tokens out, exactly m backbone forwards per BNS batch;
2. **gateway** (``gateway``) — one process: async intake queue, budget/
   shape coalescing into padded flush batches, mixed-budget shared-
   trajectory dispatch;
3. **continuous** (``continuous``) — one device's idle gaps: queued flow
   requests join IN-FLIGHT anytime trajectories at exit boundaries
   instead of waiting for the next flush;
4. **decode** (``decode``) — one engine's state slots: token-level
   continuous batching for the autoregressive engine, admit/retire per
   step;
5. **fleet** (``fleet``) — many hosts: ``FleetGateway`` federates per-host
   gateways behind one submit — the fleet-wide queue is SHARDED across
   the per-host queues, ``FleetRouter`` homes requests by budget/shape
   affinity (HRW hashing keeps assignments deterministic and jit caches
   hot), ``WorkStealer`` migrates queued work off overloaded shards, and
   hosts join/leave gracefully (bounded drain, no dropped futures).
   Routing never changes a sample: rows are independent and the fleet
   shares one uid namespace + base key, so every sample stays
   bit-identical to the single-gateway path.

Cutting across all five layers sits the **SLO plane** (``slo`` +
``stream``): attaching an ``SLOConfig`` to any gateway adds per-request
deadlines/priorities (``deadline_ms``/``priority`` on both request
types), fast-reject admission control (``AdmissionRejected``, modeled
from the registry's own dispatch-time histograms), queue shedding
(``DeadlineExceeded``), urgency-ordered planning, and — continuous tier
only — preemption of strictly-lower-priority slots at anytime exit
boundaries (the victim resumes from its saved carry, bit-identical).
``submit_stream`` yields per-exit-boundary partials (flow) or per-token
chunks (decode) and terminates with the exact settled response. With
``slo=None`` (default) every planner degenerates to the legacy FIFO
behavior byte-for-byte. See ``docs/ARCHITECTURE.md`` for the full
walkthrough and ``tests/test_overload_sim.py`` for goodput under
overload.

Cutting across all five layers sits the **observability** plane
(``repro.observability``): every tier emits into ONE ``MetricsRegistry``
schema owned by ``GatewayBase`` (each ``stats()`` dict is a projection
over a registry snapshot, and ``FleetGateway.stats()`` is the same
projection over the bucket-exact MERGE of the per-host registries), an
optional ``TraceRecorder`` captures per-request lifecycle spans
(submit -> route -> steal -> dispatch -> settle, JSONL-exportable, hop-
by-hop reconstructable for stolen requests), and ``serve.py`` exports
everything over ``--metrics-port`` (Prometheus + JSON) and
``--stats-interval`` (one shared line formatter for all modes).

Metric schema (name — type — labels — emitting tiers):

======================= ========= ============ =========================
``submitted``           counter   —            all gateways
``completed``           counter   —            all gateways
``failed``              counter   —            all gateways
``batches``             counter   —            gateway, decode
``mixed_batches``       counter   —            gateway
``forwards``            counter   —            gateway, continuous,
                                               decode
``real_rows``           counter   —            gateway, decode
``padded_rows``         counter   —            gateway, decode
``trajectories``        counter   —            continuous, decode
``legs``                counter   —            continuous
``joins``               counter   —            continuous, decode
``join_forwards``       counter   —            continuous
``slot_steps_active``   counter   —            continuous, decode
``slot_steps_total``    counter   —            continuous, decode
``tokens_out``          counter   —            decode
``cancelled``           counter   —            decode
``prefill_calls``       counter   —            decode
``prefill_tokens``      counter   —            decode
``stolen_in``           counter   —            any federated gateway
``stolen_out``          counter   —            any federated gateway
``rejected``            counter   —            all gateways (SLO)
``preemptions``         counter   —            continuous (SLO)
``deadline_misses``     counter   —            all gateways (SLO)
``goodput``             counter   —            all gateways (SLO)
``steals``              counter   —            fleet (stealer)
``steal_rounds``        counter   —            fleet (stealer)
``rerouted``            counter   —            fleet (host leave)
``dispatches``          counter   ``program``  all dispatching tiers
``zoo_hits`` etc.       counter   —            zoo (hits/loads/distills/
                                               misses/evictions/spills)
``queue_depth``         gauge     —            all gateways (lazy)
``inflight``            gauge     —            all gateways (lazy)
``jit_programs``        gauge     —            all dispatching tiers
``compilations``        gauge     —            all gateways (one
                                               process-wide listener)
``pages_in_use``        gauge     —            decode (``PageAllocator``)
``peak_pages``          gauge     —            decode (``PageAllocator``)
``page_pool_total``     gauge     —            decode (``PageAllocator``)
``wait_ms``             histogram —            all gateways (submit ->
                                               settle; count ==
                                               completed)
``host_assembly_ms``    histogram —            gateway, continuous
``device_dispatch_ms``  histogram —            gateway, continuous,
                                               decode (the enqueue)
``device_wait_ms``      histogram —            gateway, continuous,
                                               decode (the readback)
``forwards_by_rows``    counter   ``rows``     gateway, continuous
======================= ========= ============ =========================

Module map:

``engine``  — ``FlowSampler``, ``AnytimeFlowSampler``, ``DecodeEngine``
              (paged KV via ``page_size``/``paged_kernel``), plus
              ``SamplingParams``/``sample_tokens`` (temperature / top-k /
              top-p, Gumbel-max over sorted-logit cutoffs);
``zoo``     — ``SolverZoo``, the LRU SolverSpec -> SolverArtifact cache with
              directory scan, lazy distill-on-miss, preload and spill;
``gateway`` — ``GatewayBase``/``Gateway``/``BatchScheduler``: async request
              queue, budget-coalescing padded batches, mixed-budget shared-
              trajectory dispatch, shared serving metrics, fleet federation
              hooks (``federate``/``load``/``steal``/``inject``, bounded
              ``drain(timeout=)`` raising ``DrainTimeout``);
``continuous`` — ``ContinuousGateway``/``ContinuousScheduler``, flow-side
              continuous batching at anytime exit boundaries;
``decode``  — ``DecodeGateway``/``DecodeRequest``/``DecodeResponse`` and
              ``PageAllocator``: decode-side continuous batching over fixed
              state slots — chunked batched prefill, paged-KV page
              accounting (reserve at admission, free on finish, head-of-
              line blocking), per-request sampling routing;
``fleet``   — ``FleetGateway``/``FleetRouter``/``WorkStealer``: multi-host
              federation, sharded request queue, affinity routing, work
              stealing, graceful host join/leave (emulated-host CI via
              ``repro.distributed.emulate``);
``slo``     — ``SLOConfig``/``AdmissionRejected``/``DeadlineExceeded``/
              ``urgency_key``/``PausedCarry``: the pure SLO policy layer
              (deadlines, priorities, admission, shedding, preemption);
``stream``  — ``StreamSink``/``ResponseStream``/``StreamChunk``:
              incremental results riding the existing settle path;
``sharded`` — mesh placement for gateway batches (params via
              ``distributed.sharding``, batches split along the data axes);
``tiers``   — shape-tier ladder: pad near-shapes to configured rungs at
              submit so one slot pool serves heterogeneous multi-modal
              traffic, crop back to the native shape at settle;
``toy``     — protocol-complete toy sampler/engine for the tests.
"""
from repro.serving.continuous import ContinuousGateway, ContinuousScheduler
from repro.serving.decode import (
    DecodeGateway,
    DecodeRequest,
    DecodeResponse,
    PageAllocator,
)
from repro.serving.engine import (
    AnytimeFlowSampler,
    DecodeEngine,
    FlowSampler,
    SamplingParams,
    greedy_demo,
    nearest_budget,
    nearest_latent_tokens,
    sample_tokens,
)
from repro.serving.fleet import FleetGateway, FleetRouter, WorkStealer
from repro.serving.gateway import (
    BatchScheduler,
    DrainTimeout,
    Gateway,
    GatewayBase,
    GatewayStats,
    HostLoad,
    Request,
    RequestQueue,
    Response,
)
from repro.serving.slo import (
    AdmissionRejected,
    DeadlineExceeded,
    PausedCarry,
    SLOConfig,
    urgency_key,
)
from repro.serving.stream import ResponseStream, StreamChunk, StreamSink
from repro.serving.tiers import ShapeLadder, TierOversize
from repro.serving.zoo import SolverZoo, ZooStats

__all__ = ["AdmissionRejected", "AnytimeFlowSampler", "BatchScheduler",
           "ContinuousGateway", "ContinuousScheduler", "DeadlineExceeded",
           "DecodeEngine", "DecodeGateway", "DecodeRequest",
           "DecodeResponse", "DrainTimeout", "FleetGateway", "FleetRouter",
           "FlowSampler", "Gateway", "GatewayBase", "GatewayStats",
           "HostLoad", "PageAllocator", "PausedCarry", "Request",
           "RequestQueue", "Response", "ResponseStream", "SLOConfig",
           "SamplingParams", "ShapeLadder", "SolverZoo", "StreamChunk",
           "StreamSink", "TierOversize", "WorkStealer", "ZooStats",
           "greedy_demo", "nearest_budget", "nearest_latent_tokens",
           "sample_tokens", "urgency_key"]
