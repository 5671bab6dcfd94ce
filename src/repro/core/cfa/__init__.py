"""Canonical Facet Allocation (CFA) — the paper's core contribution.

Burst-friendly off-chip memory layout for tiled uniform-dependence programs:
multi-projection facets, single-assignment, data tiling and dimension
permutation (full-tile / inter-tile / intra-tile contiguity), plus the
compiler pass that turns a program spec into a read->execute->write pipeline,
the layout autotuner that searches the layout family per workload, and the
measurement machinery behind the paper's evaluation.

Public API (paper section each symbol reproduces):

Iteration-space machinery (``spaces``)
    * ``IterSpace``        — rectangular iteration space ``E`` (§IV-A).
    * ``Deps``             — uniform, all-backwards dependence pattern (§IV-D/E).
    * ``Tiling``           — rectangular tile sizes ``t_1..t_d`` (§IV-B).
    * ``facet_widths``     — facet thickness ``w_k = max_q |e_k . B_q|`` (§IV-F3).
    * ``flow_in_points``   — a tile's flow-in set ``phi_i(T)`` (appendix A).
    * ``flow_out_points``  — a tile's flow-out set ``phi_o(T)`` (appendix A).
    * ``facet_points``     — the k-th facet ``S_k(T)`` of a tile (appendix B).
    * ``neighbor_offsets`` — backward neighbor tiles by level (§IV-D).

Facet layout (``facets``)
    * ``FacetSpec``          — one facet array's permuted layout (§IV-F..I).
    * ``build_facet_specs``  — the facet family for (space, deps, tiling),
      parameterised by extension dirs and contiguity level (§IV-G/H/I).
    * ``extension_dir``      — the paper's cyclic inter-tile direction (§IV-H).
    * ``CONTIGUITY_LEVELS``  — the three cumulative levels (§IV-G/H/I).

Packing (``allocation``)
    * ``pack_facet`` / ``pack_all`` / ``unpack_into`` — canonical array <->
      facet storage converters (§IV-F4 single-assignment allocation); both
      understand the irredundant owned masks.

Irredundant & compressed storage (``irredundant``/``compress``) — the
Ferry-2024 follow-up layout as a first-class subsystem
    * ``STORAGE_MODES``     — redundant / irredundant / compressed.
    * ``owner_of``          — the deterministic ownership rule (lowest facet
      axis wins a shared point).
    * ``StorageMap`` / ``build_storage_map`` — per-facet owned masks +
      footprint accounting (``stored_elems``, ``redundancy`` == 1.0,
      ``savings``).
    * ``dedup_facets`` / ``rehydrate_facets`` — drop / refill non-owned
      slots (the bit-exactness bridge between disciplines).
    * ``IrredundantPipeline`` / ``CompressedPipeline`` — ``CFAPipeline``
      under owner-only commits and owner-resolved halo reads (+ fixed-ratio
      codec round-trip).
    * ``BlockCodec`` / ``CODECS`` / ``get_codec`` — XOR-delta bit-pack
      block codecs (pure JAX, jit-compatible).

Burst plans (``plans``)
    * ``TransferPlan``         — exact per-tile burst statistics (§V-C).
    * ``count_runs``           — maximal contiguous runs of an address set.
    * ``cfa_plan``             — CFA reads/writes, boxed per §V-C1.
    * ``cfa_piece_census``     — §IV-D/H/J flow-in piece accounting (the
      d >= 4 unmergeable pieces made countable).
    * ``original_layout_plan`` — Bayliss [16] row-major baseline (Fig. 15).
    * ``bounding_box_plan``    — Pouchet [8] bounding-box baseline (Fig. 15).
    * ``data_tiling_plan``     — Ozturk [19] block-major baseline (Fig. 15).
    * ``interior_tile``        — the representative steady-state tile (§V-C).

Bandwidth model (``bandwidth``)
    * ``BurstModel``      — ``time = sum(T_setup + bytes/BW)`` per burst (§II-E);
      ``BurstModel.time`` of a ``PortedPlan`` is the max over per-port
      schedules (ports run concurrently, §VII); ``time(..., compute_s=...,
      overlap=True)`` composes the Fig. 13 DATAFLOW pipelined tile time.
    * ``PortedPlan``      — a plan's bursts repartitioned over n ports (§VII).
    * ``BandwidthReport`` — raw/effective bandwidth of a plan (Fig. 15 axes).
    * ``overlap_speedup`` — modeled overlapped-vs-sequential gain of a plan.
    * ``AXI_ZC706``       — the paper's ZC706 AXI HP port model (§VI-A).
    * ``TPU_V5E_HBM``     — the TPU DMA adaptation target (§VI-A analogue).

Multi-port repartition (``multiport``) — §VII future work made executable
    * ``PortAssignment`` / ``assign_ports`` — LPT placement of whole facet
      arrays on ports (balance = max/mean port load).
    * ``repartition`` / ``best_repartition`` / ``PORT_STRATEGIES`` — facet-
      and burst-granular splits of a ``TransferPlan`` into a ``PortedPlan``.
    * ``port_speedup`` — modeled multi-port gain on the interior-tile plan.

Benchmarks (``programs``)
    * ``StencilProgram`` — a Table I benchmark in post-skew normal form (§IV-E).
    * ``PROGRAMS`` / ``get_program`` — the Table I suite registry
      (``get_program`` also finds ``programs.FIELD_PROGRAMS``).

Pipeline (``transform``)
    * ``CFAPipeline`` — the read->execute->write tile pipeline of §V
      (Fig. 13); built by the ``lower_backend`` pass, run by the executors.

Autotuner (``autotune``) — the §VI "which layout?" question made a subsystem
    * ``autotune``         — staged search over tilings x extension dirs x
      contiguity levels x port repartitions (``n_ports``), scored by
      ``BurstModel``, with an on-disk cache; ``score="measured"`` re-ranks
      the top candidates by measured wall-clock (``SCORE_MODES``).
    * ``LayoutCandidate`` / ``ScoredLayout`` / ``LayoutDecision`` — the search
      space, the per-candidate score, and the ranked result (which carries
      the winning ``PortAssignment`` when ``n_ports > 1``).
    * ``candidate_tilings`` / ``hand_coded_baselines`` — enumeration helpers.
    * ``CacheSchemaError`` — on-disk decision from another cache schema.

Calibration (``calibrate``) — the measured-vs-modeled verification layer
(the paper validates with *measured* throughput, §VI; Zohouri & Matsuoka
2019 show why analytic controller models drift)
    * ``measure_runs`` / ``measure_plan`` — warmup + median-of-k wall-clock
      of a burst schedule / a whole ``TransferPlan``/``PortedPlan`` on the
      host backend (one jitted copy per burst = descriptor setup analogue).
    * ``TransferSample`` / ``fit_burst_model`` / ``CalibratedModel`` — the
      measured points, the least-squares fit of (setup, peak, port
      scaling), and the resulting drop-in ``BurstModel``.
    * ``calibrate`` / ``Calibration`` / ``CalibrationError`` — the full
      sweep (synthetic grid + Table I plans x storages x ports) and its
      JSON record with per-plan modeled-vs-measured relative error.
    * ``measurement_noise`` / ``timing_unusable_reason`` — the host noise
      probe behind the timing tests' skip-with-reason fixture.

Runtime telemetry (``obs``) — what each wave, facet and port *actually*
did, as an inspectable timeline (the runtime counterpart of the CFA1xx
static verifier; Iris argues layout decisions must be justified by
observed utilization)
    * ``TraceRecorder`` / ``Span`` / ``Counters`` — structured spans
      (copy_in / execute_tile / copy_out / halo_resolve per tile, grouped
      by wave and port; the dataflow executor's prefetch/compute/commit
      as concurrent lanes) + deterministic counters that
      ``TraceRecorder.reconcile`` checks exactly against the per-tile
      ``TransferPlan`` accounting and ``BurstModel.plan_bytes``.
    * ``chrome_trace`` / ``validate_chrome_trace`` — Chrome trace-event
      JSON export (Perfetto-loadable; ``tools/cfa_trace.py`` is the CLI)
      and its schema check (``docs/tracing.md``).
    * ``RuntimeReport`` / ``runtime_report`` — measured-vs-modeled
      attribution per plan/port/facet, worst-offender ranked with the
      CFA3xx fixit vocabulary.
    * Enabled per compile via ``compile(..., trace=True)`` /
      ``REPRO_TRACE=1``; read back with ``CompiledStencil.last_trace()``
      (``PassTrace`` compile spans fold into the same timeline).

Lowering passes (``passes``) — ``compile`` as a staged compiler flow
    * ``CompileState``    — the immutable lowering artifact (request fields
      refined in place, artifacts accreted per stage).
    * ``Pass`` / ``PassPipeline`` / ``PipelineError`` — the stage protocol,
      the validated runner (duplicate/missing/mis-ordered stages rejected at
      assembly), and its loud failure mode.
    * ``PassTrace``       — one stage's trace record (name, version, wall
      time, artifact diff); ``CompiledStencil.trace()`` returns the run's
      tuple of them.
    * ``default_pipeline`` / ``DEFAULT_PASSES`` /
      ``default_pass_fingerprint`` — the pinned default lowering
      (resolve_program -> validate_target -> distribute -> layout_search ->
      storage_map -> port_repartition -> select_backend -> lower_backend)
      and its (name, version) fingerprint, the identity the autotune cache
      is keyed by (schema v7).
    * ``estimate_facet_bytes`` — the distribute pass's per-host budget
      metric (``compile(host_budget=...)`` splits over the port mesh when
      the estimate exceeds it).

Static analysis (``analysis``) — the compile-time verifier + burst lint
(Iris pairs layout generation with automated efficiency analysis; Zohouri
& Matsuoka 2019 quantify the sub-burst-length degradation CFA3xx flags)
    * ``verify`` / ``compile(..., verify=True)`` — run the analysis suite
      over a ``CompiledStencil``; ERROR diagnostics raise
      ``VerificationError``; the report rides as
      ``CompiledStencil.diagnostics()``.
    * ``Diagnostic`` / ``AnalysisReport`` / ``VerificationError`` — one
      coded, located, severity-tagged finding; the aggregate; the loud
      failure mode.
    * ``AnalysisPass`` / ``analysis_pass`` / ``DEFAULT_ANALYSES`` — the
      read-only pass category and the default suite: CFA1xx
      single-assignment/coverage proofs, CFA2xx overlap race detection,
      CFA3xx burst-efficiency lint (priced by ``BurstModel``), CFA4xx
      capability/contract checks (code table in ``docs/analysis.md``).
    * ``check_facet_family`` / ``plan_accounting`` /
      ``check_overlap_schedule`` / ``lint_plan`` — the pure checkers
      (``autotune`` discards candidates failing ``plan_accounting``).
    * ``run_analyses`` / ``verify_pipeline`` — suite runner over a
      ``CompileState``; the default lowering + analyses pipeline.
    * ``ineligible_reason`` (``executors``) — the non-raising capability
      gate CFA401 reports verbatim.

Front-end (``api``/``executors``) — one declarative entry point over it all
    * ``compile``          — a thin driver over the default pass pipeline;
      returns a ``CompiledStencil`` (callable; carries ``.layout``,
      ``.plan``, ``.report()``, ``.lower()``, ``.pipeline``, ``.trace()``).
    * ``Target`` / ``TARGETS`` / ``register_target`` / ``get_target`` — the
      platform registry (burst model + port budget).
    * ``Executor`` / ``ExecutorCaps`` / ``EXECUTORS`` / ``register_executor``
      / ``get_executor`` / ``available_backends`` / ``select_backend`` /
      ``BackendError`` — the execution-backend registry and its single
      capability gate (N-D and port-count validation).
"""
from .spaces import (
    IterSpace,
    Deps,
    Tiling,
    facet_widths,
    flow_in_points,
    flow_out_points,
    facet_points,
    neighbor_offsets,
)
from .facets import (
    FacetSpec,
    build_facet_specs,
    extension_dir,
    CONTIGUITY_LEVELS,
)
from .allocation import pack_facet, pack_all, unpack_into
from .compress import BlockCodec, CODECS, get_codec
from .irredundant import (
    STORAGE_MODES,
    StorageMap,
    build_storage_map,
    owner_of,
    dedup_facets,
    rehydrate_facets,
    IrredundantPipeline,
    CompressedPipeline,
)
from .plans import (
    TransferPlan,
    count_runs,
    cfa_plan,
    cfa_piece_census,
    original_layout_plan,
    bounding_box_plan,
    data_tiling_plan,
    interior_tile,
)
from .bandwidth import (
    BurstModel,
    PortedPlan,
    BandwidthReport,
    AXI_ZC706,
    TPU_V5E_HBM,
    overlap_speedup,
)
from .multiport import (
    PortAssignment,
    PORT_STRATEGIES,
    assign_ports,
    repartition,
    best_repartition,
    port_speedup,
)
from .programs import StencilProgram, PROGRAMS, get_program
from .autotune import (
    LayoutCandidate,
    ScoredLayout,
    LayoutDecision,
    CacheSchemaError,
    SCORE_MODES,
    autotune,
    candidate_tilings,
    hand_coded_baselines,
)
from .calibrate import (
    TransferSample,
    CalibratedModel,
    Calibration,
    CalibrationError,
    measure_runs,
    measure_plan,
    fit_burst_model,
    calibrate,
    measurement_noise,
    timing_unusable_reason,
)
from .obs import (
    Span,
    Counters,
    TraceRecorder,
    RuntimeReport,
    runtime_report,
    chrome_trace,
    validate_chrome_trace,
)
from .transform import CFAPipeline
from .passes import (
    CompileState,
    Pass,
    PassPipeline,
    PassTrace,
    PipelineError,
    DEFAULT_PASSES,
    default_pipeline,
    default_pass_fingerprint,
    estimate_facet_bytes,
)
from .executors import (
    BackendError,
    Executor,
    ExecutorCaps,
    EXECUTORS,
    register_executor,
    get_executor,
    available_backends,
    ineligible_reason,
    select_backend,
)
from .analysis import (
    Diagnostic,
    AnalysisReport,
    VerificationError,
    AnalysisPass,
    analysis_pass,
    DEFAULT_ANALYSES,
    check_facet_family,
    plan_accounting,
    check_overlap_schedule,
    lint_plan,
    run_analyses,
    verify,
    verify_pipeline,
)
from .api import (
    Target,
    TARGETS,
    register_target,
    get_target,
    compile,
    CompiledStencil,
)

__all__ = [
    "IterSpace", "Deps", "Tiling", "facet_widths",
    "flow_in_points", "flow_out_points", "facet_points", "neighbor_offsets",
    "FacetSpec", "build_facet_specs", "extension_dir", "CONTIGUITY_LEVELS",
    "pack_facet", "pack_all", "unpack_into",
    "STORAGE_MODES", "StorageMap", "build_storage_map", "owner_of",
    "dedup_facets", "rehydrate_facets",
    "IrredundantPipeline", "CompressedPipeline",
    "BlockCodec", "CODECS", "get_codec",
    "TransferPlan", "count_runs", "cfa_plan", "cfa_piece_census", "original_layout_plan",
    "bounding_box_plan", "data_tiling_plan", "interior_tile",
    "BurstModel", "PortedPlan", "BandwidthReport", "AXI_ZC706", "TPU_V5E_HBM",
    "overlap_speedup",
    "PortAssignment", "PORT_STRATEGIES", "assign_ports",
    "repartition", "best_repartition", "port_speedup",
    "StencilProgram", "PROGRAMS", "get_program",
    "LayoutCandidate", "ScoredLayout", "LayoutDecision", "CacheSchemaError",
    "SCORE_MODES", "autotune", "candidate_tilings", "hand_coded_baselines",
    "TransferSample", "CalibratedModel", "Calibration", "CalibrationError",
    "measure_runs", "measure_plan", "fit_burst_model", "calibrate",
    "measurement_noise", "timing_unusable_reason",
    "Span", "Counters", "TraceRecorder", "RuntimeReport", "runtime_report",
    "chrome_trace", "validate_chrome_trace",
    "CFAPipeline",
    "CompileState", "Pass", "PassPipeline", "PassTrace", "PipelineError",
    "DEFAULT_PASSES", "default_pipeline", "default_pass_fingerprint",
    "estimate_facet_bytes",
    "BackendError", "Executor", "ExecutorCaps", "EXECUTORS",
    "register_executor", "get_executor", "available_backends",
    "ineligible_reason", "select_backend",
    "Diagnostic", "AnalysisReport", "VerificationError",
    "AnalysisPass", "analysis_pass", "DEFAULT_ANALYSES",
    "check_facet_family", "plan_accounting", "check_overlap_schedule",
    "lint_plan", "run_analyses", "verify", "verify_pipeline",
    "Target", "TARGETS", "register_target", "get_target",
    "compile", "CompiledStencil",
]
