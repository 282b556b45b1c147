"""Global configuration flags for alpa_tpu.

TPU-native analog of the reference's ``alpa/global_env.py:5-139`` GlobalConfig
singleton.  Unlike the reference there is no driver->Ray-worker snapshot sync
(``update_worker_config``): under jax.distributed every host process reads the
same environment, so flags are plain process-local state seeded from env vars.
"""
import os


def _env_bool(name: str, default: bool) -> bool:
    val = os.environ.get(name)
    if val is None:
        return default
    return val.lower() in ("1", "true", "yes", "on")


class GlobalConfig:
    """Process-global configuration object.

    Mirrors the flag surface of the reference GlobalConfig where the concept
    survives the TPU redesign; NCCL/Ray/cupy flags are intentionally absent.
    """

    def __init__(self):
        # ---------- backend ----------
        # "tpu" | "cpu" | "gpu".  Used to pick the jax platform for meshes.
        self.backend = os.environ.get("ALPA_TPU_BACKEND", None)  # None = jax default

        # ---------- compilation ----------
        # Print compilation phase timings (ref: debug_compilation_time).
        self.print_compilation_time = _env_bool("ALPA_TPU_PRINT_COMPILATION_TIME", False)
        # Dump intermediate jaxprs / HLO to this dir if set.
        self.dump_debug_info_dir = os.environ.get("ALPA_TPU_DUMP_DIR", None)
        # Use static cost model instead of on-device profiling for auto stage
        # construction (ref: HloCostModelProfileWorker path).  Default True on
        # TPU: spinning up submeshes to profile is slow (SURVEY.md hard part 2).
        self.use_hlo_cost_model = _env_bool("ALPA_TPU_USE_HLO_COST_MODEL", True)
        # Path to a JSON ProfilingResultDatabase (mesh_profiling.profile_all).
        self.profiling_database_filename = os.environ.get(
            "ALPA_TPU_PROF_DATABASE", None)
        # Time limit (seconds) handed to the ILP solver.
        self.ilp_time_limit = int(os.environ.get("ALPA_TPU_ILP_TIME_LIMIT", "600"))
        # Weight-update (ZeRO) sharding stage: "auto" lets the ILP choose
        # sharded optimizer state by cost (memory term vs all-gather
        # traffic), "0" disables it, "2" shards optimizer state over the
        # data-parallel axis, "3" also shards parameters.  Seeds
        # AutoShardingOption.zero_stage.
        self.zero_stage = os.environ.get("ALPA_TPU_ZERO_STAGE", "auto")

        # ---------- runtime ----------
        # Cross-mesh resharding strategy: "send_recv" | "broadcast".
        # (ref: global_config.resharding_mode)
        self.resharding_mode = os.environ.get("ALPA_TPU_RESHARDING_MODE", "send_recv")
        # How RESHARD instructions move data: "device_put" lets the jax
        # runtime carry the transfer; "planned" drives the tile plan
        # literally (per-tile routed transfers + local-allgather /
        # broadcast legs, ref SymbolicReshardingTask :418) with byte
        # accounting.  "planned" is the validating mode; device_put is the
        # production fast path.
        self.resharding_execution = os.environ.get(
            "ALPA_TPU_RESHARDING_EXEC", "device_put")
        # Load-balancing mode for resharding send selection:
        # "normal" | "no_loadbalance".
        self.resharding_loadbalance_mode = os.environ.get(
            "ALPA_TPU_RESHARDING_LOADBALANCE", "normal")
        # Pipeline instruction dispatch:
        # "auto" | "registers" | "overlap" | "sequential" | "threaded".
        # "registers" replays the build-time register-file lowering (flat
        # slot buffers + precomputed index tuples + cached resharding
        # executors — no dict hashing or sharding resolution per call);
        # "overlap" replays the lowering's instruction dataflow graph
        # with cross-mesh RESHARDs launched eagerly on a transfer pool
        # the moment their producers retire (bounded in-flight window);
        # "threaded" runs the emitter's per-mesh instruction streams on
        # worker threads (the per-host stream analog of ref
        # runtime_emitter's per-worker lists); "auto" picks overlap when
        # eligible (register-eligible AND multi-mesh with cross-mesh
        # RESHARDs AND overlap_resharding), else registers when eligible
        # (single process, device_put resharding), and falls back to the
        # interpreter otherwise.  Tracing, fault injection, and race
        # checking do NOT change the mode: they compile in as per-node
        # hooks on the graph executor (ISSUE 6), so instrumented runs
        # execute the same fast path.  Multi-process always dispatches
        # sequentially: collectives must be issued in the same order on
        # every process.
        self.pipeline_dispatch_mode = os.environ.get(
            "ALPA_TPU_PIPELINE_DISPATCH", "auto")
        # Runtime race detection: threaded dispatch reports every
        # worker's instruction accesses through DispatchRaceChecker;
        # register/overlap replay arms the SlotHazardChecker graph-node
        # hook (slot read/write/free conflicts against in-flight
        # transfers).  A detected race (a partitioner dependency bug)
        # raises instead of corrupting numerics.  Debug tool — adds
        # per-instruction bookkeeping.
        self.debug_dispatch_races = _env_bool(
            "ALPA_TPU_DEBUG_DISPATCH_RACES", False)
        # Collect per-instruction trace events on the dispatch hot loop
        # (any mode — recorded via the unified telemetry recorder and
        # exported by dump_stage_execution_trace).
        self.collect_trace = _env_bool("ALPA_TPU_COLLECT_TRACE", False)
        # Static plan verification (ISSUE 8): every lowered register-file
        # program runs the alpa_tpu.analysis.plan_verifier analyses (slot
        # typing, cross-mesh deadlock freedom, liveness/leaks, structural
        # invariants) at compile time.  "error" blocks compilation on any
        # finding; "warn" (default) logs and continues; "off" skips.
        # Zero dispatch-replay cost either way — the verifier never runs
        # on the hot path.
        self.verify_plans = os.environ.get(
            "ALPA_TPU_VERIFY_PLANS", "warn")
        # Fifth analysis (ISSUE 13): explicit-state model checking of
        # every lowered plan's stream interleavings under real
        # SEND/RECV FIFO channel semantics.  "all" model-checks every
        # plan; "fixture" (default) only plans small enough to finish
        # in well under a second (<= model_check.FIXTURE_MAX_OPS ops);
        # "off" skips the analysis.  Findings merge into the same
        # PlanVerdict and obey the verify_plans policy above.
        self.verify_plans_model_check = os.environ.get(
            "ALPA_TPU_VERIFY_MODEL_CHECK", "fixture")
        # DFS state budget for the model checker.  Exhaustion degrades
        # to partial coverage (reported as a model.budget-exhausted
        # note + the `partial` stat), never an error.
        self.model_check_state_budget = int(os.environ.get(
            "ALPA_TPU_MODEL_CHECK_BUDGET", "50000"))
        # Sixth analysis (ISSUE 14): numerics certification — a
        # precision-flow abstract interpretation composing the lossy
        # transfer codec's documented error bounds end to end.  "warn"
        # (default) reports findings through the verify_plans policy;
        # "error" blocks _launch with PlanVerificationError on any
        # numerics.* error finding even when verify_plans itself only
        # warns; "off" skips the analysis.
        self.verify_plans_numerics = os.environ.get(
            "ALPA_TPU_VERIFY_NUMERICS", "warn")
        # Per-tensor worst-case relative-error budget (fraction of the
        # codec's block max) the numerics analysis certifies every
        # value's composed bound against; crossing it raises a
        # numerics.budget-exceeded finding.
        self.numerics_error_budget = float(os.environ.get(
            "ALPA_TPU_NUMERICS_ERROR_BUDGET", "0.05"))
        # Seventh analysis (ISSUE 15): translation validation — prove
        # every lowered plan computes the source jaxpr by symbolic
        # execution over hash-consed opaque stage-application terms,
        # modulo the documented rewrite axioms (accumulation
        # reassociation/commutation, resharding identity).  "warn"
        # (default) reports findings through the verify_plans policy;
        # "error" blocks _launch with PlanVerificationError on any
        # equiv.* error finding even when verify_plans itself only
        # warns; "off" skips the analysis.
        self.verify_plans_equiv = os.environ.get(
            "ALPA_TPU_VERIFY_EQUIV", "warn")
        # Hash-consed term budget for the translation validation.
        # Exhaustion degrades to a partial verdict (an
        # equiv.budget-exhausted note + the `partial` stat), never an
        # error.
        self.equiv_term_budget = int(os.environ.get(
            "ALPA_TPU_EQUIV_TERM_BUDGET", "100000"))
        # Whether pipeshard runtime overlaps resharding with compute by
        # issuing transfers as soon as producers finish.  This is the
        # gate for the "overlap" dispatch mode under
        # pipeline_dispatch_mode="auto": set False to pin auto on the
        # synchronous register replay.
        self.overlap_resharding = _env_bool(
            "ALPA_TPU_OVERLAP_RESHARDING", True)
        # In-flight transfer window for overlap dispatch (caps how many
        # cross-mesh RESHARDs may be launched but unwaited, bounding
        # staging memory).  0 = auto: use the pipeline schedule's
        # overlap_window_hint().
        self.overlap_inflight_window = int(os.environ.get(
            "ALPA_TPU_OVERLAP_WINDOW", "0"))
        # Cross-mesh RESHARD lowering strategy (ISSUE 7): "auto" picks
        # per edge from the two shardings: direct_p2p where that move is
        # chip to chip, else the candidate whose wire leg is
        # (aligned_relayout), priced among several by the collective
        # cost model; forcing "direct_p2p" | "slice_all_gather" |
        # "all_to_all" | "reduce_scatter_gather" | "aligned_relayout"
        # pins every edge where the strategy is eligible (ineligible
        # edges fall back to direct_p2p).
        self.reshard_strategy = os.environ.get(
            "ALPA_TPU_RESHARD_STRATEGY", "auto")
        # Lossy transfer codec for cross-mesh ACTIVATION edges (ISSUE 7):
        # "off" | "int8" | "fp8".  Opt-in; applies only to fp32/bf16
        # payloads at least reshard_quantize_min_bytes large, and never
        # to microbatch-invariant values (weights, consts, grad
        # accumulators).  Error bounds: pipeline_parallel/reshard_codec.
        self.reshard_quantize = os.environ.get(
            "ALPA_TPU_RESHARD_QUANTIZE", "off")
        # Minimum payload bytes before the transfer codec applies.
        self.reshard_quantize_min_bytes = int(os.environ.get(
            "ALPA_TPU_RESHARD_QUANTIZE_MIN_BYTES", "65536"))
        # Quantized GRADIENT collectives (ISSUE 19; EQuARX-style):
        # "off" | "int8" | "fp8".  Opt-in: the auto-sharding ILP prices
        # quantized vs full-precision gradient all-reduce /
        # reduce-scatter per tensor and the numerics certifier composes
        # the codec's stochastic-rounding ERROR_BOUND into the
        # end-to-end budget.  "off" produces byte-identical plans,
        # fingerprints, and cache keys.
        self.grad_quantize = os.environ.get(
            "ALPA_TPU_GRAD_QUANTIZE", "off")
        # Minimum gradient tensor bytes before the gradient codec
        # applies; smaller tensors aren't bandwidth-bound and keep the
        # full-precision collective.
        self.grad_quantize_min_bytes = int(os.environ.get(
            "ALPA_TPU_GRAD_QUANTIZE_MIN_BYTES", "65536"))
        # Error feedback for quantized gradients: carry the
        # quantization residual into the next step's quantization so
        # cumulative error stays at the single-shot bound (the numerics
        # analysis amortizes the bound accordingly).  On by default
        # whenever grad_quantize is enabled.
        self.grad_error_feedback = os.environ.get(
            "ALPA_TPU_GRAD_ERROR_FEEDBACK", "on") != "off"

        # ---------- profile-guided replanning (ISSUE 12) ----------
        # Close the loop from measured step performance back into the
        # planners (telemetry/calibration.py): "off" plans from the
        # analytic cost models exactly as before (byte-identical plans,
        # unchanged cache keys); "suggest" consults the measured-cost
        # calibration store and logs the predicted critical-path delta
        # of a replan without applying it; "auto" re-solves with
        # measured costs and hot-swaps the new plan through the compile
        # cache + plan-fingerprint machinery (the static plan verifier
        # re-runs on the swapped plan).
        self.replan_mode = os.environ.get("ALPA_TPU_REPLAN_MODE", "off")
        # Minimum ingested samples before a calibrated entry overrides
        # its analytic prediction; below this the planners fall back to
        # the analytic model.
        self.calibration_min_samples = int(os.environ.get(
            "ALPA_TPU_CALIBRATION_MIN_SAMPLES", "3"))
        # On-disk tier of the calibration store (one JSON file per
        # entry, atomic writes, content-addressed like the compile
        # cache).  Unset = memory-only: measurements calibrate this
        # process but do not persist across restarts.
        self.calibration_dir = os.environ.get(
            "ALPA_TPU_CALIBRATION_DIR", None)

        # ---------- certified plan superoptimization (ISSUE 17) ------
        # Post-lowering rewrite engine over RegisterFileProgram
        # (analysis/superopt.py): instruction re-scheduling, FREE
        # sinking/hoisting, transfer fusion/fission, recompute flips —
        # scored by simulate_dag over calibrated costs and accepted
        # only when the seven-analysis verdict introduces no new
        # (analysis, code) finding vs the baseline.  "off" skips the
        # engine entirely (byte-identical plans); "suggest" searches
        # and reports (superopt.txt, alpa_superopt_* metrics) without
        # applying; "auto" swaps the accepted rewritten program in.
        self.superopt_mode = os.environ.get(
            "ALPA_TPU_SUPEROPT_MODE", "off")
        # Beam width of the greedy rewrite search.
        self.superopt_beam_width = int(os.environ.get(
            "ALPA_TPU_SUPEROPT_BEAM", "4"))
        # Rewrite-step budget: total candidates the search may score.
        self.superopt_step_budget = int(os.environ.get(
            "ALPA_TPU_SUPEROPT_STEPS", "32"))
        # Max candidate lowerings the verdict gate may run per compile
        # (each gate check re-lowers + re-verifies one candidate).
        self.superopt_verify_budget = int(os.environ.get(
            "ALPA_TPU_SUPEROPT_VERIFY_BUDGET", "2"))
        # Transfer-fission cap: max members per batched same-edge
        # RESHARD group (0 = unlimited, the historical coalescer
        # behavior).  Oversized groups serialize behind the
        # overlap_inflight_window; capping lets the search split them.
        self.superopt_max_group = int(os.environ.get(
            "ALPA_TPU_SUPEROPT_MAX_GROUP", "0"))

        # ---------- elastic training (ISSUE 16) ----------
        # ElasticSupervisor budgets (alpa_tpu/elastic.py; see
        # docs/fault_tolerance.md#elastic-training).  Step budget: max
        # committed steps an episode may lose (checkpoint cadence must
        # keep the replay distance under this); exceeding it is recorded
        # in alpa_elastic_budget_violations_total, it never blocks the
        # resume itself.
        self.elastic_step_budget = int(os.environ.get(
            "ALPA_TPU_ELASTIC_STEP_BUDGET", "4"))
        # Wall-clock budget (seconds) for one detect -> resume episode.
        self.elastic_time_budget_s = float(os.environ.get(
            "ALPA_TPU_ELASTIC_TIME_BUDGET", "300"))
        # Preemption grace window (seconds): on a preemption *notice*
        # the supervisor snapshots synchronously and must land the write
        # inside this window for the snapshot to count as before-kill.
        self.elastic_grace_period_s = float(os.environ.get(
            "ALPA_TPU_ELASTIC_GRACE", "30"))
        # How long quiesce() may wait for in-flight pipeshard launches
        # to drain before the episode proceeds with a torn step (the
        # restore path makes that safe — resume replays from the last
        # verified checkpoint either way).
        self.elastic_quiesce_timeout_s = float(os.environ.get(
            "ALPA_TPU_ELASTIC_QUIESCE_TIMEOUT", "60"))
        # Checkpoint every N successful steps while supervised (1 =
        # every step; the replay distance after a failure is at most
        # this interval, so keep it <= elastic_step_budget).
        self.elastic_snapshot_interval = int(os.environ.get(
            "ALPA_TPU_ELASTIC_SNAPSHOT_INTERVAL", "1"))
        # WedgeDetector probe timeout (seconds): a probe that neither
        # answers nor errors inside this window classifies the device
        # as wedged, not dead.
        self.wedge_probe_timeout_s = float(os.environ.get(
            "ALPA_TPU_WEDGE_PROBE_TIMEOUT", "120"))

        # ---------- compile cache ----------
        # On-disk tier of the persistent compile cache (ILP auto-sharding
        # solutions, stage-DP decisions, parallel_plan artifacts — see
        # alpa_tpu/compile_cache.py).  Unset = memory-only cache; set a
        # directory to make warm restarts skip the solvers.
        self.compile_cache_dir = os.environ.get("ALPA_TPU_CACHE_DIR", None)
        # Master switch for the compile cache (both tiers).
        self.compile_cache_enabled = _env_bool(
            "ALPA_TPU_COMPILE_CACHE", True)
        # In-memory LRU capacity (entries) of the compile cache.
        self.compile_cache_memory_entries = int(os.environ.get(
            "ALPA_TPU_COMPILE_CACHE_MEM_ENTRIES", "128"))

        # ---------- telemetry ----------
        # Span tracing master switch (alpa_tpu/telemetry/trace.py).
        # Checked as a module-level flag before any allocation: the
        # register-replay hot path stays within 2% of the no-telemetry
        # baseline when this is off (guarded in tier-1).
        self.telemetry_enabled = _env_bool("ALPA_TPU_TRACE", False)
        # Cap on buffered events per TraceRecorder store (spans /
        # instants / counters each); overflow increments a drop counter
        # in the exported trace instead of growing without bound.
        self.telemetry_max_events = int(os.environ.get(
            "ALPA_TPU_TRACE_MAX_EVENTS", "200000"))
        # Flight recorder (alpa_tpu/telemetry/flight.py): fixed-size
        # lock-free ring of the last N instruction events, auto-dumped
        # when a step raises, a fault site fires, or the watchdog
        # declares a mesh SUSPECT.  Cheap enough to leave on in
        # production (one counter bump + one tuple store per
        # instruction), hence default True.
        self.flight_recorder = _env_bool("ALPA_TPU_FLIGHT", True)
        # Ring capacity (instruction events retained); rounded up to a
        # power of two.
        self.flight_recorder_capacity = int(os.environ.get(
            "ALPA_TPU_FLIGHT_CAPACITY", "4096"))
        # Where auto-dumps land.  None = dump_debug_info_dir, else the
        # system temp dir.
        self.flight_dump_dir = os.environ.get("ALPA_TPU_FLIGHT_DIR", None)
        # Chip peak bf16 TFLOPS used by the MFU attribution
        # (telemetry/perf.py).  0 = auto-detect from the
        # TPU generation via mesh_profiling.TPU_GENERATION_SPECS; set
        # explicitly for CPU/emulated runs so stage-MFU numbers stay
        # meaningful.
        self.device_peak_tflops = float(os.environ.get(
            "ALPA_TPU_DEVICE_PEAK_TFLOPS", "0"))

        # ---------- serving: paged KV cache + router (ISSUE 11) ------
        # Master switch: controller replicas build their streaming
        # engines over a serve.kv_cache.KVBlockPool (fixed-size token
        # blocks, refcounted block tables, upfront reservation).  Decode
        # stays bit-exact vs the unpaged engine.
        self.kv_paged = _env_bool("ALPA_TPU_KV_PAGED", False)
        # Tokens per KV block; must divide the model's seq_len.
        self.kv_block_size = int(os.environ.get(
            "ALPA_TPU_KV_BLOCK_SIZE", "16"))
        # Pool capacity in blocks; 0 = auto (two engine batches' worth:
        # one for live sequences, one of headroom for cached prefixes).
        self.kv_cache_blocks = int(os.environ.get(
            "ALPA_TPU_KV_CACHE_BLOCKS", "0"))
        # Cross-request prefix reuse: full prompt/output blocks are
        # published to a hash-chain index (LRU-evicted under pressure);
        # admissions sharing a token prefix skip recomputing those
        # blocks.  Off keeps paging but recomputes every prompt, and
        # preserves the legacy one-static-PrefixHandle register_model
        # semantics (docs/serving.md).
        self.kv_prefix_reuse = _env_bool("ALPA_TPU_KV_PREFIX_REUSE", True)
        # serve.router placement policy: "least_loaded" scores replicas
        # by queue depth + in-flight + tokens; "round_robin" rotates.
        self.router_policy = os.environ.get(
            "ALPA_TPU_ROUTER_POLICY", "least_loaded")
        # Per-replica saturation: a replica whose request p99 exceeds
        # this (milliseconds) is routed around; 0 disables the check.
        self.router_shed_ttft_ms = float(os.environ.get(
            "ALPA_TPU_ROUTER_SHED_TTFT_MS", "0"))
        # Per-replica saturation: queue depth above which a replica is
        # routed around; requests shed (503) only when EVERY healthy
        # replica is saturated.  0 disables.
        self.router_shed_queue_depth = int(os.environ.get(
            "ALPA_TPU_ROUTER_SHED_QUEUE_DEPTH", "64"))
        # Consecutive failed /healthz probes before a replica is
        # dropped from rotation (one clean probe restores it).
        self.router_health_fail_threshold = int(os.environ.get(
            "ALPA_TPU_ROUTER_HEALTH_FAILS", "3"))
        # Autoscale hooks: sliding evaluation window (seconds) over
        # aggregate queue depth...
        self.router_autoscale_window_s = float(os.environ.get(
            "ALPA_TPU_ROUTER_AUTOSCALE_WINDOW", "30"))
        # ...sustained above hi fires on_want_more, sustained below lo
        # fires on_want_fewer (per-replica averages).
        self.router_autoscale_hi_queue = float(os.environ.get(
            "ALPA_TPU_ROUTER_AUTOSCALE_HI_QUEUE", "8"))
        self.router_autoscale_lo_queue = float(os.environ.get(
            "ALPA_TPU_ROUTER_AUTOSCALE_LO_QUEUE", "1"))

        # ---------- serving: disaggregated prefill/decode (ISSUE 18) -
        # Phase-split serving (serve.disagg): "off" keeps the monolithic
        # path byte-identical; "auto" splits whenever the router has at
        # least one prefill-phase AND one decode-phase replica; "forced"
        # requires both pools and sheds (503) when either is missing.
        self.disagg_mode = os.environ.get("ALPA_TPU_DISAGG_MODE", "off")
        # KV handoff payload codec over the wire: "off" ships the block
        # bytes verbatim (bit-exact decode, the default); "int8"/"fp8"
        # ride the reshard_codec blockwise quantizer (lossy within its
        # ERROR_BOUND — docs/serving.md#disaggregated-prefilldecode).
        self.disagg_codec = os.environ.get("ALPA_TPU_DISAGG_CODEC", "off")
        # Decode-pool backpressure: when the decode pool's aggregate
        # depth (queued + in-flight) exceeds this, NEW prefill
        # admissions shed (503) — handoffs already produced are never
        # dropped.  0 disables.
        self.disagg_backpressure_depth = int(os.environ.get(
            "ALPA_TPU_DISAGG_BACKPRESSURE_DEPTH", "0"))
        # Prefill-pool SLO: route around a prefill replica whose
        # router-measured TTFT p99 exceeds this (ms).  0 disables.
        self.disagg_ttft_slo_ms = float(os.environ.get(
            "ALPA_TPU_DISAGG_TTFT_SLO_MS", "0"))
        # Decode-pool SLO: route around a decode replica whose
        # inter-token p99 exceeds this (ms).  0 disables.
        self.disagg_itl_slo_ms = float(os.environ.get(
            "ALPA_TPU_DISAGG_ITL_SLO_MS", "0"))
        # Handoff artifacts retained per prefill engine for corrupt-
        # artifact re-fetch / decode-replica re-ingest (LRU once full;
        # the router acks artifacts as streams finish).
        self.disagg_retain_artifacts = int(os.environ.get(
            "ALPA_TPU_DISAGG_RETAIN_ARTIFACTS", "64"))

    def show(self):
        return {k: v for k, v in self.__dict__.items()}


global_config = GlobalConfig()
