"""Timeouts, intervals and wire constants.

Capability parity with reference ``utils/constants.py:5-34``: every timeout the
reference exposes has an equivalent here, though several lose their reason to
exist on TPU (in-program collectives cannot "time out per image"); they are
kept for the HTTP control plane and the multi-host job path.
"""

# --- job collection (control-plane / multi-host HTTP path) -----------------
WORKER_JOB_TIMEOUT = 10.0        # s to wait per image when draining a job queue
JOB_COMPLETION_TIMEOUT = 60.0    # s overall for a remote participant's results
TILE_COLLECTION_TIMEOUT = 60.0   # s overall for tile gathering
TILE_WAIT_TIMEOUT = 30.0         # s per tile when draining the tile queue
TILE_TRANSFER_TIMEOUT = 30.0     # s for a single tile HTTP transfer
TILE_SEND_TIMEOUT = 60.0         # s client-side timeout when POSTing tiles
QUEUE_INIT_TIMEOUT = 5.0         # s for queue creation on the server loop

# --- transport retry --------------------------------------------------------
SEND_MAX_RETRIES = 5
SEND_BACKOFF_BASE = 0.5          # s; exponential, capped
SEND_BACKOFF_CAP = 5.0
# full jitter on the backoff (delay *= uniform[0.5, 1.0]): a fleet of
# workers whose sends all failed at the same instant (master restart,
# overloaded NIC) must not retry in lockstep — synchronized retry storms
# are exactly what the chaos harness exposes under overload
SEND_JITTER_FRACTION = 0.5
# per-attempt wall-clock cap: a caller-provided timeout larger than
# this is still split into <=cap attempts, so one black-holed
# connection can't eat the whole retry budget.  Sized to the LARGEST
# legitimate single transfer (TILE_SEND_TIMEOUT / JOB_COMPLETION: a
# slow link really can need 60s for an image-set upload) — the cap
# must bound pathology, never shrink a transfer that was always legal
SEND_ATTEMPT_TIMEOUT_CAP = 60.0
# a Retry-After header on 429/503 overrides the computed backoff (the
# server knows its own drain rate better than our exponential guess);
# bounded so a hostile/buggy peer can't park a sender for minutes
RETRY_AFTER_CAP_S = 60.0

# --- worker lifecycle -------------------------------------------------------
PROCESS_TERMINATION_TIMEOUT = 5.0
PROCESS_WAIT_TIMEOUT = 3.0
WORKER_CHECK_INTERVAL = 2.0      # s between liveness polls
STATUS_CHECK_INTERVAL = 5.0
WORKER_STARTUP_DELAY = 2.0       # s before auto-launching workers
WORKER_STARTUP_WATCH_S = 120.0   # s a launch watches for an early exit
MEMORY_CLEAR_DELAY = 0.5
PREFLIGHT_TIMEOUT = 0.3          # s health probe before dispatch

# --- IO ---------------------------------------------------------------------
CHUNK_SIZE = 8192
LOG_TAIL_BYTES = 65536

# --- mesh defaults ----------------------------------------------------------
# node types whose presence makes a graph "distributed" — the fan-out /
# prune root set (reference findCollectorConnectedNodes, gpupanel.js:987).
# Single source of truth for the executor (SPMD gating) and dispatcher
# (worker pruning): the two must never disagree on what fans out.
SEED_NODE_TYPES = ("DistributedSeed",)
COLLECTOR_NODE_TYPES = ("DistributedCollector",)
UPSCALER_NODE_TYPES = ("UltimateSDUpscaleDistributed",)
DISTRIBUTED_NODE_TYPES = COLLECTOR_NODE_TYPES + UPSCALER_NODE_TYPES

DATA_AXIS = "data"       # replica fan-out (reference: one worker process each)
TENSOR_AXIS = "tensor"   # intra-op model parallelism (no reference analog)
SEQ_AXIS = "seq"         # sequence/context parallelism (ring attention)
TILE_AXIS = DATA_AXIS    # tiles shard over the same physical axis as replicas

# --- wire formats -----------------------------------------------------------
TENSOR_WIRE_DTYPE = "float32"
IMAGE_WIRE_FORMAT = "png"        # lossless, reference parity (compress_level=0)
# raw-tensor fast path on the worker->master hop: npy payload compressed
# with zstd when available, else deflate (the container may lack the
# zstandard module; utils.image gates on import).  Negotiated per master
# via GET /distributed/wire_formats — peers that don't advertise it get
# PNG, exactly the reference wire.
TENSOR_WIRE_CONTENT_TYPE = "application/x-dtpu-tensor"
WIRE_FORMAT_ENV = "DTPU_WIRE"    # "png" forces the compatibility format

# --- overlapped execution pipeline ------------------------------------------
# Batch-coalescing scheduler + compute/host-IO overlap (server/app.py,
# workflow/scheduler.py).  Envs resolve at ServerState construction so
# tests can pin either path.
MAX_QUEUE_ENV = "DTPU_MAX_QUEUE"         # /prompt backpressure cap
MAX_QUEUE_DEFAULT = 256                  # full queue -> HTTP 429
DRAIN_TIMEOUT_ENV = "DTPU_DRAIN_TIMEOUT_S"
DRAIN_TIMEOUT_DEFAULT = 30.0             # graceful-shutdown drain bound
OVERLAP_ENV = "DTPU_OVERLAP"             # "0" -> serial (host work inline)
COALESCE_ENV = "DTPU_COALESCE"           # "0" -> one prompt per dispatch
COALESCE_MAX_ENV = "DTPU_MAX_COALESCE"
COALESCE_MAX_DEFAULT = 8                 # largest batched prompt group
HOSTIO_THREADS_ENV = "DTPU_HOSTIO_THREADS"
HOSTIO_THREADS_DEFAULT = 2               # encoder/uploader pool width
HOSTIO_PENDING_ENV = "DTPU_HOSTIO_PENDING"
HOSTIO_PENDING_DEFAULT = 16              # bounded: submit blocks past this

# Node types the batch-coalescing scheduler may merge along the data
# axis.  Deliberately conservative: every type here is batch-parallel
# (per-sample math; no cross-sample state, no HTTP side channel), the
# only batch SOURCE is EmptyLatentImage (so multiplying its batch_size
# scales the whole graph), and per-prompt variation is confined to the
# KSampler seed widget (masked out of the coalescing signature).
# Anything else runs one-prompt-per-dispatch — correctness first.
COALESCE_SAFE_NODE_TYPES = frozenset({
    "CheckpointLoaderSimple", "CLIPTextEncode", "CLIPSetLastLayer",
    "LoraLoader", "LoraLoaderModelOnly", "EmptyLatentImage", "KSampler",
    "VAEDecode", "VAEDecodeTiled", "SaveImage", "PreviewImage",
    # the prompt expander in front of a text encode, treated as the
    # encode is: its text and seed are in the signature, so merged
    # prompts share one expansion as they share one embedding
    "LanguageModelLoader", "LanguageModelGenerate",
})

# --- iteration-level continuous batching (workflow/batch_executor.py) --------
# Orca-style step-granular denoise executor: a persistent, padded,
# shape-bucketed device batch (bucket key = the PR 2 structural
# signature) where each slot carries one prompt's iteration state —
# remaining-steps counter, sigma index and its exact (seed, fold-idx)
# noise-stream keys, so a continuously-batched image stays bit-identical
# to its serial run.  New prompts JOIN the running batch at the next
# step boundary (non-contiguous same-signature merging); finished
# prompts exit their slot immediately and proceed to VAE decode on the
# tail thread without draining the batch.  Off by default (DTPU_CB=1
# opts in): the legacy head-run coalescing dispatch stays the default
# path, so existing deployments see no behavior change.
CB_ENV = "DTPU_CB"                       # "1" arms the step executor
CB_SLOTS_ENV = "DTPU_CB_SLOTS"           # slots per bucket (max batch)
CB_SLOTS_DEFAULT = 4
# padded slot-count bucket set: each step runs at the smallest declared
# pad >= the active slot count, so the per-step executable comes from a
# FIXED shape set (zero steady-state retraces once each pad compiled);
# sizes above DTPU_CB_SLOTS are ignored, and the max is always included
CB_PAD_BUCKETS_ENV = "DTPU_CB_PAD_BUCKETS"
CB_PAD_BUCKETS_DEFAULT = "1,2,4,8"
CB_MAX_BUCKETS_ENV = "DTPU_CB_MAX_BUCKETS"  # concurrent shape buckets
CB_MAX_BUCKETS_DEFAULT = 4
# admission window: how long the driver lingers at an idle boundary
# waiting for arrivals to accumulate before dispatching the first step
# (0 = dispatch immediately; a small value trades first-step latency
# for fuller initial batches under bursty arrivals)
CB_ADMIT_WINDOW_ENV = "DTPU_CB_ADMIT_WINDOW_S"
CB_ADMIT_WINDOW_DEFAULT = 0.0
# samplers with an extracted single-step callable (models/samplers.py
# SAMPLER_STEPS): the ONLY samplers the step executor admits — every
# entry is stateless across steps (no multistep history carry), so a
# slot's step N is a pure function of (x, sigma_N, sigma_N+1, keys)
CB_SAFE_SAMPLERS = frozenset({"euler", "ddim", "euler_ancestral"})
# --- latent paging + SLO-aware preemption (ISSUE 17) -------------------------
# The vLLM/PagedAttention lesson around the UNCHANGED step kernel: a CB
# slot's full truth is tiny and explicit (latent row, sigma index,
# remaining steps, per-row PRNG key), so a batch/free-tier slot can be
# PARKED to host at a step boundary — freeing HBM-backed slot capacity
# for a paid burst — and RESUMED later bit-identically.  The admissible
# working set (started jobs) may then exceed physical slots; a per-step
# residency scheduler decides which rows occupy slots, ordered by the
# PR 9 tenant classes.  Off by default; requires DTPU_CB=1 too.
CB_PARK_ENV = "DTPU_CB_PARK"             # "1" arms paging/preemption
# bound on host-parked rows across all buckets (each is one latent +
# key row set — small, but the registry must not grow without limit)
CB_PARK_MAX_ENV = "DTPU_CB_PARK_MAX"
CB_PARK_MAX_DEFAULT = 64
# device-memory residency bar (PR 5 telemetry): parked rows resume only
# while bytes_in_use/bytes_limit stays BELOW this fraction, and slots
# page OUT (lowest class first) while above it.  Unknown limits (CPU,
# host_rss fallback) read as headroom — the gate is a TPU-HBM guard,
# not a host-memory one.
CB_PARK_HBM_FRACTION_ENV = "DTPU_CB_PARK_HBM_FRACTION"
CB_PARK_HBM_FRACTION_DEFAULT = 0.9
# preempt order over TENANT_CLASSES: leftmost pages out first, and a
# class may only preempt classes listed BEFORE its own position —
# "batch < free < paid", with paid absent from the list: never paged.
CB_PREEMPT_ORDER = ("batch", "free")

# --- cross-request compute reuse (runtime/reuse.py) ---------------------------
# Three content-addressed cache tiers + the SSE preview/cancellation
# channel.  DTPU_CACHE=0 is a TRUE kill switch (no key computed, no
# cache touched on any hot path — the DTPU_RESOURCE=0 pattern); each
# tier has its own LRU byte budget, and the resource monitor samples
# the total into a bounded ``cache_bytes`` ring so residency is
# observable next to RSS/HBM.
CACHE_ENV = "DTPU_CACHE"                 # "0" disables every tier
CACHE_BYTES_ENV = "DTPU_CACHE_BYTES"     # exact-hit result tier budget
CACHE_BYTES_DEFAULT = 256 << 20
CACHE_DEVICE_BYTES_ENV = "DTPU_CACHE_DEVICE_BYTES"  # on-device sub-graph tier
CACHE_DEVICE_BYTES_DEFAULT = 128 << 20
CACHE_TILE_BYTES_ENV = "DTPU_CACHE_TILE_BYTES"      # refined-tile tier
CACHE_TILE_BYTES_DEFAULT = 256 << 20
CACHE_ENTRIES_ENV = "DTPU_CACHE_ENTRIES"  # per-tier entry cap
CACHE_ENTRIES_DEFAULT = 256
# progressive previews over SSE (GET /distributed/preview/<prompt_id>):
# the continuous-batching denoise driver publishes a cheap latent->RGB
# frame at step boundaries WHILE a subscriber is attached; a client
# that disconnects mid-stream abandons the job (its CB slot exits at
# the next step boundary; queued copies are purged).
PREVIEW_ENV = "DTPU_PREVIEW"             # "0" disables the SSE route
PREVIEW_EVERY_ENV = "DTPU_PREVIEW_EVERY"  # publish every N steps
PREVIEW_EVERY_DEFAULT = 1
PREVIEW_MAX_CLIENTS_ENV = "DTPU_PREVIEW_MAX_CLIENTS"
PREVIEW_MAX_CLIENTS_DEFAULT = 64

# Node types whose output is a pure function of (widgets, upstream
# content keys) — the sub-graph memoization's addressable set
# (runtime/reuse.node_key).  Deliberately conservative: these feed
# the two cached producers (text-encoder embeddings via CLIPTextEncode,
# VAE-encoded conditioning via VAEEncode).  LoadImage is addressable
# through a file-stat salt (name + mtime + size), so a re-upload under
# the same name misses instead of aliasing.
REUSE_KEY_NODE_TYPES = frozenset({
    "CheckpointLoaderSimple", "CLIPSetLastLayer", "LoraLoader",
    "LoraLoaderModelOnly", "CLIPTextEncode", "CLIPTextEncodeSDXL",
    "CLIPTextEncodeSDXLRefiner", "LoadImage", "VAEEncode",
    "ImageScale", "EmptyLatentImage",
})

# Node types a whole graph may consist of and still be EXACT-HIT result
# cacheable (tier a): every type is a deterministic pure function of
# its widgets/inputs (seeded samplers included), with the only
# out-of-graph state — LoadImage's file — folded into the key as a
# stat salt.  Distributed nodes never qualify (their outputs depend on
# fleet topology and per-dispatch hidden state), and neither does
# SaveImage: its contract is a NEW counter-numbered file on disk per
# queue, a side effect a replay cannot honor from stored arrays —
# SaveImage graphs execute every time, only collect-in-memory graphs
# (PreviewImage) replay.
RESULT_CACHE_SAFE_NODE_TYPES = (COALESCE_SAFE_NODE_TYPES | frozenset({
    "LoadImage", "VAEEncode", "VAEEncodeTiled", "ImageScale",
    "CLIPTextEncodeSDXL", "CLIPTextEncodeSDXLRefiner",
    "KSamplerAdvanced",
})) - frozenset({"SaveImage"})

# --- observability (request-scoped tracing + telemetry) ----------------------
# Dapper-style always-on request tracing (utils/trace.py spans): every job
# gets a trace; spans propagate over the distributed HTTP edges via
# W3C-traceparent headers and land in a bounded per-job flight recorder
# served by GET /distributed/trace/<prompt_id>.
TRACE_ENV = "DTPU_TRACE"                 # "0" disables span creation
TRACE_RING_ENV = "DTPU_TRACE_RING"       # flight-recorder ring size
TRACE_RING_DEFAULT = 128                 # completed job traces retained
TRACE_MAX_SPANS = 512                    # per-trace span cap (then dropped)
TRACEPARENT_HEADER = "traceparent"       # W3C trace-context header name
SLOW_JOB_ENV = "DTPU_SLOW_JOB_S"         # >0: always-on slow-job log line
LOG_JSON_ENV = "DTPU_LOG_JSON"           # "1": JSON log lines with trace ids
METRICS_RESET_ENV = "DTPU_METRICS_RESET"  # "0" disables POST .../metrics/reset

# Fixed latency-histogram bucket bounds (seconds) shared by the JSON
# percentiles and the Prometheus exposition: 1 ms .. 60 s exponential-ish,
# wide enough for a CPU-tiny step and a real SDXL compile alike.
HISTOGRAM_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                       0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

# --- continuous capture plane (utils/trace_export.py) ------------------------
# Durable trace export: committed flight-recorder traces stream to
# rotating, size-bounded, schema-versioned JSONL capture files — the
# record half of the record/replay plan (ROADMAP item 6).  Off unless an
# export dir is set; appends are fsync-free and happen on the
# finalizer/executor threads, never the event loop.
TRACE_EXPORT_DIR_ENV = "DTPU_TRACE_EXPORT_DIR"       # unset/empty: off
TRACE_EXPORT_SEGMENT_ENV = "DTPU_TRACE_EXPORT_SEGMENT_BYTES"
TRACE_EXPORT_SEGMENT_DEFAULT = 4 * 1024 * 1024       # rotate past 4 MiB
TRACE_EXPORT_RETAIN_ENV = "DTPU_TRACE_EXPORT_RETAIN_BYTES"
TRACE_EXPORT_RETAIN_DEFAULT = 64 * 1024 * 1024       # dir cap (oldest out)
TRACE_EXPORT_SCHEMA = 1                              # capture-file schema
TRACE_EXPORT_PREFIX = "capture-"                     # segment file prefix
# no-silent-caps: ring evictions and export drops log once per N
TRACE_EVICT_LOG_EVERY = 50
TRACE_EXPORT_DROP_LOG_EVERY = 20

# --- SLO burn-rate engine (utils/slo.py) -------------------------------------
# Declarative per-tenant-class objectives evaluated over multi-window
# rolling rings (fast ~5m / slow ~1h), fed by the finalize path.  Spec
# grammar: "class:obj,obj;class:obj" where obj is pNN<DURs (latency:
# at most (100-NN)% of requests slower than DUR) or completion>RATIO
# (success fraction), e.g. "paid:p95<2s,completion>0.999;free:p95<10s".
SLO_SPEC_ENV = "DTPU_SLO_SPEC"           # unset/empty: engine disarmed
SLO_FAST_WINDOW_ENV = "DTPU_SLO_FAST_S"
SLO_FAST_WINDOW_DEFAULT = 300.0          # fast burn window (~5m)
SLO_SLOW_WINDOW_ENV = "DTPU_SLO_SLOW_S"
SLO_SLOW_WINDOW_DEFAULT = 3600.0         # slow burn window (~1h)
SLO_RING_MAX = 4096                      # samples kept per tenant window
AUTOSCALE_SLO_ENV = "DTPU_AUTOSCALE_SLO"  # "1": paid fast burn>1 scales up

# CB flight deck: per-bucket step-boundary occupancy timeline ring
# (busy/parked/free + admits/retires/preemptions deltas per boundary)
# in the batching snapshot, rendered by `cli flightdeck`.
CB_DECK_RING_ENV = "DTPU_CB_DECK_RING"
CB_DECK_RING_DEFAULT = 128               # boundaries retained

# --- resource telemetry plane (utils/resource.py) ----------------------------
# Device-memory / host-RSS / utilization sampling into bounded in-memory
# ring timeseries (the Gorilla model: operational telemetry is only
# useful cheap, aggregated and recent), current-value gauges on both
# metrics surfaces, per-job HBM attribution in ExecutionResult + trace
# attrs, and fleet federation: heartbeats carry a snapshot, the master
# retains the latest per worker and serves the merged view on
# GET /distributed/cluster/metrics{,.prom} with worker_id labels.
RESOURCE_ENV = "DTPU_RESOURCE"           # "0" disables the monitor thread
RES_INTERVAL_ENV = "DTPU_RES_INTERVAL_S"
RES_INTERVAL_DEFAULT = 5.0               # s between monitor samples
RES_RING_ENV = "DTPU_RES_RING"
RES_RING_DEFAULT = 720                   # samples per series (~1h @ 5s)
# federation pull-through cache: a worker snapshot older than this (it
# missed a heartbeat) is re-pulled live from the worker's
# GET /distributed/resource — and the pulled value is cached back into
# the registry so repeated scrapes inside the TTL don't re-pull
RES_FED_TTL_ENV = "DTPU_RES_FED_TTL_S"
RES_FED_TTL_DEFAULT = 10.0

# --- fault-tolerant cluster control plane (runtime/cluster.py) ---------------
# Worker registry with leases: a worker is HEALTHY while its lease (renewed
# by heartbeat/probe/data-plane contact) is fresh, SUSPECT after
# DTPU_SUSPECT_PROBES consecutive failed probes, DEAD once the lease
# expires.  The per-job work ledger records which participant owns which
# tile indices / seed slices; on lease expiry or collection deadline the
# unfinished units are redispatched to healthy participants (master
# included) instead of being dropped.
LEASE_ENV = "DTPU_LEASE_S"
LEASE_DEFAULT = 15.0             # s a worker stays alive without contact
SUSPECT_PROBES_ENV = "DTPU_SUSPECT_PROBES"
SUSPECT_PROBES_DEFAULT = 2       # consecutive failed probes -> suspect
# reassign: redispatch lost units (the default); partial: the seed's
# partial-result-on-timeout behavior; fail: raise instead of degrading
FAULT_POLICY_ENV = "DTPU_FAULT_POLICY"
FAULT_POLICY_DEFAULT = "reassign"
FAULT_POLICIES = ("reassign", "partial", "fail")
# Hedged straggler dispatch ("The Tail at Scale"): once a job is
# >= DTPU_HEDGE_PCT % complete and a unit's owner has been silent longer
# than DTPU_HEDGE_FACTOR x the ledger's moving per-unit latency estimate,
# speculatively re-issue the unit to an idle participant; the ledger's
# exactly-once check-in makes the first completion win.
HEDGE_ENV = "DTPU_HEDGE"                 # "0" disarms hedging
HEDGE_PCT_ENV = "DTPU_HEDGE_PCT"
HEDGE_PCT_DEFAULT = 50.0                 # % complete before hedging arms
HEDGE_FACTOR_ENV = "DTPU_HEDGE_FACTOR"
HEDGE_FACTOR_DEFAULT = 3.0               # x latency estimate -> overdue
# floor under the overdue threshold: batched check-ins collapse the
# inter-arrival EMA toward zero, and without a floor the happy path
# hedges sub-second units — speculative work must stay idle unless a
# unit is ACTUALLY late.  Conservative by default (hedging trades
# duplicate compute for tail latency; a false hedge also forces a
# recovery-shaped recompile on the master); tune down for clusters
# with tight, well-known unit latencies.
HEDGE_MIN_WAIT_ENV = "DTPU_HEDGE_MIN_WAIT_S"
HEDGE_MIN_WAIT_DEFAULT = 5.0
CLUSTER_POLL_S = 0.25            # drain poll granularity with recovery armed
HEARTBEAT_FRACTION = 3.0         # workers heartbeat every lease/this
CLUSTER_TRANSITIONS_KEPT = 64    # registry transition-history ring
LEDGER_COMPLETED_KEPT = 32       # finished-job summary ring
MASTER_URL_ENV = "DTPU_MASTER_URL"   # worker -> master heartbeat target
WORKER_ID_ENV = "DTPU_WORKER_ID"     # this worker's config identity
# test/bench-only fault injection, JSON: {"drop_tiles_after": k} makes a
# worker die after sending k tiles; {"stall_s": t} delays its first send
FAULT_INJECT_ENV = "DTPU_FAULT_INJECT"

# --- durable job state + master failover (runtime/durable.py) ----------------
# Write-ahead job log: every queue admission, ledger ownership transition,
# unit check-in and idempotency-key stamp is appended as a checksummed
# record to segment files under DTPU_WAL_DIR (unset = durability off, the
# default — tests and single-shot CLIs pay nothing).  A restarting master
# replays snapshot+log into a reconstructed queue/WorkLedger and resumes
# in-flight jobs, redispatching only unfinished units; a standby
# (DTPU_STANDBY=1) watches the master's lease file in the same dir and
# takes over on expiry.  Fencing: WAL appends carry the holder's epoch
# and are refused once a higher epoch has acquired the lease.
WAL_DIR_ENV = "DTPU_WAL_DIR"
# fsync policy: "always" (default — a record is durable before the caller
# is acked), "off" (leave it to the OS; crash loses the page-cache tail),
# or a float seconds value (group fsync: at most that much ack'd-but-
# volatile history)
WAL_SYNC_ENV = "DTPU_WAL_SYNC"
WAL_SYNC_DEFAULT = "always"
WAL_SEGMENT_BYTES_ENV = "DTPU_WAL_SEGMENT_BYTES"
WAL_SEGMENT_BYTES_DEFAULT = 1 << 20    # rotate (and snapshot) at 1 MiB
STANDBY_ENV = "DTPU_STANDBY"           # "1": observe the lease, don't acquire
MASTER_LEASE_ENV = "DTPU_MASTER_LEASE_S"
MASTER_LEASE_DEFAULT = 10.0            # s the master lease lives unrenewed
MASTER_LEASE_FRACTION = 3.0            # renew every lease/this
WAL_FENCE_CHECK_S = 0.25               # lease-file fence re-read cadence
WAL_OWNER_ENV = "DTPU_MASTER_ID"       # lease owner identity (default: master)

# --- SLO-aware multi-tenant admission (workflow/scheduler.py) ----------------
# Priority classes with weighted fair dequeue + class-aware shedding.
# Unlabelled traffic defaults to the HIGHEST class so a single-tenant
# deployment keeps the plain DTPU_MAX_QUEUE backpressure semantics
# (paid sheds only at a genuinely full queue); tag requests with
# {"priority": "free"|"batch"} to opt into the lower classes.
TENANT_CLASSES = ("paid", "free", "batch")
TENANT_DEFAULT_CLASS_ENV = "DTPU_TENANT_DEFAULT_CLASS"
TENANT_DEFAULT_CLASS = "paid"
# dequeue weights (stride scheduling): out of 10 scheduled groups under
# backlog, ~6 are paid, ~3 free, ~1 batch.  "paid=6,free=3,batch=1".
TENANT_WEIGHTS_ENV = "DTPU_TENANT_WEIGHTS"
TENANT_WEIGHTS_DEFAULT = {"paid": 6.0, "free": 3.0, "batch": 1.0}
# class-aware shedding: a class is 429'd once queue occupancy
# (depth/max_queue) reaches its threshold — batch is shed first, free
# under deeper overload, paid only when the queue is ACTUALLY full.
TENANT_SHED_ENV = "DTPU_TENANT_SHED"      # "batch=0.5,free=0.85,paid=1"
TENANT_SHED_DEFAULT = {"paid": 1.0, "free": 0.85, "batch": 0.5}
# per-client token buckets (admission rate limiting): sustained
# prompts/s and burst size per client_id.  0/unset = unlimited (the
# back-compat default); per-class overrides via "paid=10,free=2".
TENANT_RATE_ENV = "DTPU_TENANT_RATE"
TENANT_BURST_ENV = "DTPU_TENANT_BURST"
TENANT_BURST_DEFAULT = 10.0
TENANT_BUCKETS_KEPT = 1024       # LRU bound on per-client bucket state
# deadline-aware hedging: a request carrying {"slo_s": N} stamps its
# distributed jobs with a deadline; the hedge-overdue threshold is then
# re-keyed on the REMAINING SLO budget (hedge a unit silent longer than
# SLO_HEDGE_FRACTION x the budget left) instead of the global
# DTPU_HEDGE_FACTOR, and the min-progress gate is waived — a job about
# to blow its deadline hedges its first straggler, not just its last.
SLO_HEDGE_FRACTION_ENV = "DTPU_SLO_HEDGE_FRACTION"
SLO_HEDGE_FRACTION_DEFAULT = 0.25    # hedge when silent > 25% of budget left
SLO_MIN_WAIT_S = 0.25                # floor: never hedge sub-250ms silences

# --- elastic-fleet autoscaler (runtime/autoscale.py) -------------------------
# Reconciliation loop on the master: spawn workers when federated queue
# depth / device utilization exceed thresholds for a sustained window,
# retire them by drain + lease non-renewal.  Off by default
# (DTPU_AUTOSCALE=1 arms it in serve()); every decision lands in a
# bounded ring + GLOBAL_COUNTERS and the /distributed/fleet route.
AUTOSCALE_ENV = "DTPU_AUTOSCALE"             # "1" arms the loop in serve()
AUTOSCALE_INTERVAL_ENV = "DTPU_AUTOSCALE_INTERVAL_S"
AUTOSCALE_INTERVAL_DEFAULT = 5.0
AUTOSCALE_MIN_ENV = "DTPU_AUTOSCALE_MIN"     # floor on worker count
AUTOSCALE_MIN_DEFAULT = 0
AUTOSCALE_MAX_ENV = "DTPU_AUTOSCALE_MAX"     # ceiling on worker count
AUTOSCALE_MAX_DEFAULT = 4
# hysteresis: scale up when queue depth per participant exceeds
# UP_QUEUE (or utilization exceeds UP_UTIL) for WINDOW consecutive
# samples; scale down only when BOTH fall below the (strictly lower)
# DOWN thresholds for the same sustained window.  COOLDOWN after any
# action blocks the next one, so an oscillating signal can't flap.
AUTOSCALE_UP_QUEUE_ENV = "DTPU_AUTOSCALE_UP_QUEUE"
AUTOSCALE_UP_QUEUE_DEFAULT = 4.0             # queued prompts per participant
AUTOSCALE_DOWN_QUEUE_ENV = "DTPU_AUTOSCALE_DOWN_QUEUE"
AUTOSCALE_DOWN_QUEUE_DEFAULT = 1.0
AUTOSCALE_UP_UTIL_ENV = "DTPU_AUTOSCALE_UP_UTIL"
AUTOSCALE_UP_UTIL_DEFAULT = 0.85             # device-utilization fraction
AUTOSCALE_DOWN_UTIL_ENV = "DTPU_AUTOSCALE_DOWN_UTIL"
AUTOSCALE_DOWN_UTIL_DEFAULT = 0.30
AUTOSCALE_WINDOW_ENV = "DTPU_AUTOSCALE_WINDOW"
AUTOSCALE_WINDOW_DEFAULT = 3                 # consecutive samples over bar
AUTOSCALE_COOLDOWN_ENV = "DTPU_AUTOSCALE_COOLDOWN_S"
AUTOSCALE_COOLDOWN_DEFAULT = 30.0
AUTOSCALE_DRAIN_ENV = "DTPU_AUTOSCALE_DRAIN_S"
AUTOSCALE_DRAIN_DEFAULT = 30.0               # retirement drain bound
# a direction reversal within this window of the previous action counts
# as a FLAP (the convergence failure the bench asserts is zero)
AUTOSCALE_FLAP_S = 60.0
AUTOSCALE_DECISIONS_KEPT = 128               # decision-ring bound
WORKER_STATE_RETIRING = "retiring"           # registry state during drain

# --- multi-master sharded control plane (runtime/shard.py) -------------------
# N *active* masters each own a shard of the prompt-id space via a
# consistent-hash ring (virtual nodes).  DTPU_SHARD_ID arms the plane on
# a master; DTPU_SHARD_PEERS names the full member map (self included)
# as "id=url,id=url".  Each shard keeps its OWN WAL/epoch stream under
# DTPU_SHARD_WAL_ROOT/<id>; a failed master's shard is taken over by a
# ring peer (its consistent-hash successor) through the existing
# MasterLease path: the peer bumps the dead shard's epoch, replays its
# WAL, re-homes its workers and removes the member from the ring.  Ring
# state is gossiped between masters and exposed at GET /distributed/ring;
# a thin stateless router (`cli router`) spreads /prompt admission by
# prompt-id hash, with single-hop forwarding for mis-routed submissions.
SHARD_ID_ENV = "DTPU_SHARD_ID"         # this master's shard identity
SHARD_PEERS_ENV = "DTPU_SHARD_PEERS"   # "m0=http://h:p,m1=..." (incl self)
SHARD_WAL_ROOT_ENV = "DTPU_SHARD_WAL_ROOT"  # shared root; WAL = root/<id>
SHARD_VNODES_ENV = "DTPU_SHARD_VNODES"      # virtual nodes per member
# sized for placement balance: at 512 vnodes a 3-member ring splits the
# keyspace ~33/34/34% (64 vnodes skews to ~27/37/36, which caps the
# 3-master scaling win well below the bench bar); ring build is ~3 ms
SHARD_VNODES_DEFAULT = 512
SHARD_GOSSIP_ENV = "DTPU_SHARD_GOSSIP_S"    # ring-gossip interval
SHARD_GOSSIP_DEFAULT = 2.0
# a peer silent on gossip for this long is marked down in the ring view
# (reachability only — shard TAKEOVER keys on its master lease expiring)
SHARD_PEER_DOWN_ENV = "DTPU_SHARD_PEER_DOWN_S"
SHARD_PEER_DOWN_DEFAULT = 10.0
SHARD_TAKEOVER_ENV = "DTPU_SHARD_TAKEOVER"  # "0": watch only, never absorb
# ring-designated fleet-autoscale actuator: the shard owning this
# sentinel key is the ONLY one that spawns/retires on the merged
# backlog signal (every master folds the same gossiped depths into its
# signal — N independent actuators would react N times to one backlog)
AUTOSCALE_ACTUATOR_KEY = "dtpu-fleet-autoscale-actuator"
# worker -> many-master heartbeats: one lease per master shard, so a
# worker death is detected and recovered independently per shard
MASTER_URLS_ENV = "DTPU_MASTER_URLS"   # comma list; overrides MASTER_URL
# stateless admission router (`cli router` / runtime/shard.build_router_app)
ROUTER_MASTERS_ENV = "DTPU_ROUTER_MASTERS"  # seed master URLs (comma list)
ROUTER_REFRESH_ENV = "DTPU_ROUTER_REFRESH_S"  # ring re-pull cadence
ROUTER_REFRESH_DEFAULT = 5.0
# single-hop forwarding marker: a /prompt carrying this header is never
# forwarded again (the ring views disagreed; the receiver keeps the job)
SHARD_FORWARD_HEADER = "x-dtpu-forwarded-from"

# --- chaos fault-injection harness (utils/chaos.py) --------------------------
# Env/route-driven fault injection on the HTTP edges and worker
# lifecycle, for tests (tests/test_overload.py).  DTPU_CHAOS is a
# JSON spec; unset = zero overhead (one dict lookup per edge).  Fields:
#   {"drop_pct": 5, "delay_pct": 5, "delay_s": 0.2, "http_5xx_pct": 5,
#    "corrupt_pct": 2, "freeze_heartbeats": true|["w0"],
#    "routes": ["/distributed/tile_complete", ...], "seed": 1234}
# pcts are 0-100 fractions of matching edges; "routes" scopes the
# server-side injection (default: the data-plane + /prompt edges);
# "seed" makes a run reproducible.  Every injection bumps a
# chaos_* GLOBAL_COUNTERS event (both metrics surfaces).
CHAOS_ENV = "DTPU_CHAOS"
CHAOS_SEED_ENV = "DTPU_CHAOS_SEED"
CHAOS_DEFAULT_ROUTES = ("/prompt", "/distributed/tile_complete",
                        "/distributed/job_complete",
                        "/distributed/heartbeat")
CHAOS_DELAY_DEFAULT_S = 0.25

# --- env-var registry (dtpu-lint env-undeclared / env-readme-drift) ----------
# Every DTPU_* environment variable the package reads must be declared
# here as a string literal AND carry a row in the README env table —
# the static-analysis gate (comfyui_distributed_tpu/analysis) enforces
# both directions, so neither side can drift.  The entries below are
# read at their point of use (models/, parallel/, cli) rather than
# through this module; declaring them here is the registry, not a
# refactor.

# multi-host bring-up (parallel/mesh.initialize_multihost)
COORDINATOR_ENV = "DTPU_COORDINATOR"        # host:port -> jax.distributed
NUM_PROCESSES_ENV = "DTPU_NUM_PROCESSES"    # pod process count
PROCESS_ID_ENV = "DTPU_PROCESS_ID"          # this host's process index
# serve-path mesh layout (parallel/mesh.axes_from_env, ISSUE 16): full
# shape ("data=2,tensor=2" or positional "2x2x1") or the tensor-size
# shorthand; unset keeps the pure data-parallel default
MESH_SHAPE_ENV = "DTPU_MESH_SHAPE"
TP_ENV = "DTPU_TP"
# model plane (models/)
DEFAULT_FAMILY_ENV = "DTPU_DEFAULT_FAMILY"  # family override (tests: tiny)
BF16_WEIGHTS_ENV = "DTPU_BF16_WEIGHTS"      # bf16 weight storage toggle
JIT_CACHE_CAP_ENV = "DTPU_JIT_CACHE_CAP"    # per-pipeline jit cache bound
LORA_CACHE_CAP_ENV = "DTPU_LORA_CACHE_CAP"  # parsed-LoRA cache bound
TP_MIN_SHARD_ELEMENTS_ENV = "DTPU_TP_MIN_SHARD_ELEMENTS"  # TP leaf floor
ATTN_SCORES_BYTES_ENV = "DTPU_ATTN_SCORES_BYTES"  # attn chunking ceiling
RING_MIN_TOKENS_ENV = "DTPU_RING_MIN_TOKENS"  # ring-attention seq floor
# runtime/serving odds and ends
INTERRUPT_POLL_ENV = "DTPU_INTERRUPT_POLL"  # force per-step poll on/off
WARMUP_ENV = "DTPU_WARMUP"                  # serve-startup warmup JSON
MODELS_DIR_ENV = "DTPU_MODELS"              # cli --models-dir default
MASTER_PID_ENV_NAME = "DTPU_MASTER_PID"     # spawned-worker master watch

# --- traffic twin / deterministic fleet simulator (sim/, ISSUE 19) -----------
# The discrete-event simulator that runs the real policy code against a
# virtual clock.  All three knobs are read by sim/ at point of use:
SIM_SEED_ENV = "DTPU_SIM_SEED"              # overrides the scenario's seed
SIM_MAX_EVENTS_ENV = "DTPU_SIM_MAX_EVENTS"  # runaway-scenario backstop
SIM_MAX_EVENTS_DEFAULT = 5_000_000
SIM_EVENT_LOG_TAIL_ENV = "DTPU_SIM_EVENT_LOG_TAIL"  # human-readable tail
SIM_EVENT_LOG_TAIL_DEFAULT = 256            # full log feeds the digest
# calibration gate (tests/test_sim.py::TestCalibration): max tolerated
# mean relative error between the sim and the measured records
# (benchmarks/scenarios/*.measured.json)
SIM_CALIBRATION_MAX_ERR = 0.15

# --- critical-path analytics plane (utils/trace_analysis.py, ISSUE 20) ------
# Turns recorded traces into critical-path blame: per-trace category
# decomposition with an unattributed-gap residual, cross-trace profiles,
# regression diffs and baseline-gated anomaly detection.  The live plane
# is armed by pointing DTPU_ANALYSIS_BASELINE at a committed profile
# JSON; everything else is on-demand (cli why / cli analyze / the
# /distributed/analysis route).
ANALYSIS_BASELINE_ENV = "DTPU_ANALYSIS_BASELINE"   # unset/empty: disarmed
ANALYSIS_ANOMALY_PCT_ENV = "DTPU_ANALYSIS_ANOMALY_PCT"
ANALYSIS_ANOMALY_PCT_DEFAULT = 50.0     # per-category regression bar (%)
ANALYSIS_STRAGGLER_X_ENV = "DTPU_ANALYSIS_STRAGGLER_X"
ANALYSIS_STRAGGLER_X_DEFAULT = 2.0      # worker p95 vs fleet-median bar
ANALYSIS_MAX_TRACES_ENV = "DTPU_ANALYSIS_MAX_TRACES"
ANALYSIS_MAX_TRACES_DEFAULT = 256       # records per aggregation pass
# clock-skew correction for cross-process edges: heartbeats carry the
# worker's wall clock, the master min-filters (offset + one-way delay)
# samples into a per-worker estimate and applies it when ingesting
# shipped worker spans.  "0" records estimates but never shifts spans.
SKEW_CORRECTION_ENV = "DTPU_SKEW_CORRECTION"
SKEW_SAMPLES_KEPT = 16                  # min-filter window per worker

# --- span-attribute whitelist (dtpu-lint span-attr) ---------------------------
# The vocabulary contract between span producers and the trace readers
# (`cli trace`, the flight-recorder consumers): every literal attr key
# stamped on a span anywhere in the package must be listed here, so a
# new attr is a conscious API addition, not drive-by drift.
TRACE_ATTR_WHITELIST = frozenset({
    # job identity / topology
    "prompt_id", "client_id", "tenant", "role", "fanout", "job",
    "worker", "node", "target",
    # coalescing / continuous batching
    "coalesced", "coalesced_into", "bucket", "slot",
    # latent paging + SLO-aware preemption (ISSUE 17): the sigma index a
    # row parked/resumed at, and what displaced it
    "step", "preempted_by",
    # SLO burn-rate engine (ISSUE 18): slo_breach event marks a job that
    # exceeded its class's latency objective
    "threshold_s",
    # recovery / hedging
    "lost", "to", "units", "tile_idx", "n_workers",
    # resource attribution (ISSUE 5)
    "device_peak_mb", "rss_mb", "mem_peak_mb", "mem_peak_delta_mb",
    "mem_source",
    # cross-request compute reuse (ISSUE 13)
    "cache_hit", "cache_tier", "tiles_skipped",
    # multi-master sharded control plane (ISSUE 14)
    "shard", "ring_epoch", "forwarded_from",
    # clock-skew-corrected ingest (ISSUE 20): the offset (ms) applied to
    # a shipped worker span forest, stamped on the receive event
    "skew_ms",
})

# --- persistent compilation cache -------------------------------------------
# (directory rule: runtime/manager.enable_persistent_compile_cache)
# only persist compilations worth the disk round trip; 0 also caches the
# tiny convert/broadcast jits (useful in tests, noisy in production)
COMPILE_CACHE_MIN_COMPILE_SECS = 0.5
