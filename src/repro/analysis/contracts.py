"""Declared contracts the analysis passes check the repo against.

This file is the single place a new engine axis, entry point, kernel
package, or observability field must be registered. The passes cross-check
these tables against the AST, so forgetting to update a table is itself a
finding (``AX106``/``AX108``): adding an axis to a jitted entry point's
``static_argnames`` without declaring it here fails ``--check``, and
declaring it here without giving every entry point a spec (or an explicit
``n/a`` waiver) fails too. That is the "flag any entry point a new axis
missed" guarantee.

Axis-spec schema (one row per entry point, one cell per axis):

* ``dict(param=..., forward=..., via=..., sinks=..., require_direct=...)``
  -- the entry accepts the axis. ``param`` (default: the axis name) is the
  parameter that carries it (e.g. ``backend`` travels as ``engine=`` on the
  dispatchers, ``mechanism`` as ``mode=`` on the jitted PS-DSF entries).
  ``via="kwargs"`` means the axis rides the entry's ``**kwargs``.
  ``forward=True`` requires the value to reach a callee. ``sinks`` lists
  the functions that must validate the axis when the entry dispatches
  through a registry (the callee is not statically resolvable there);
  ``require_direct=True`` additionally demands validation in the entry
  itself (used where one backend path consumes the axis locally).
* a string -- an explicit waiver: the axis genuinely does not apply to
  this entry, and the string is the one-line justification.
"""
from __future__ import annotations

#: the eight hand-threaded engine axes (ROADMAP PRs 1-10)
AXES = ("mechanism", "backend", "placement", "fill", "round", "layout",
        "precision", "accel")

#: every registered allocator — ``engine.solve``/``sched`` dispatch through
#: ``get_allocator`` (a statically unresolvable registry call), so the axis
#: pass grounds their kwargs-borne axes against ALL of these sinks: each
#: one must validate the axis itself.
_ALLOCATOR_SINKS = ("solve_psdsf_rdm", "solve_psdsf_tdm", "solve_cdrfh",
                    "solve_tsf", "solve_cdrf", "_drf", "_uniform")

_F64 = "n/a — float64 end-to-end; precision is a DistributedPSDSF tick knob"
_IS_JAX = "n/a — this IS the jax backend; backend dispatch is engine.solve"
_IS_NUMPY = ("n/a — numpy implementation; backend dispatch lives in "
             "engine.solve")
_FULL_LAYOUT = "n/a — `buckets=None` is the full layout"

ENTRY_POINTS = {
    ("src/repro/core/engine.py", "solve"): {
        "mechanism": dict(forward=True),
        "backend": dict(forward=False),
        "placement": dict(forward=True),
        "fill": dict(via="kwargs", forward=True,
                     sinks=_ALLOCATOR_SINKS + ("_solve_psdsf_via_jax",
                                               "solve_baseline_jax")),
        # the numpy sweep path consumes round= in solve itself (Gauss-
        # Seidel by construction), hence require_direct on top of the
        # jax-path and closed-form sinks
        "round": dict(via="kwargs", forward=True, require_direct=True,
                      sinks=("_drf", "_uniform", "_solve_psdsf_via_jax",
                             "solve_baseline_jax")),
        "layout": dict(via="kwargs", forward=True,
                       sinks=_ALLOCATOR_SINKS + ("_solve_psdsf_via_jax",
                                                 "solve_baseline_jax")),
        "precision": _F64,
        "accel": dict(via="kwargs", forward=True,
                      sinks=_ALLOCATOR_SINKS + ("_solve_psdsf_via_jax",
                                                "solve_baseline_jax")),
    },
    ("src/repro/core/psdsf.py", "solve_psdsf_rdm"): {
        "mechanism": "n/a — this function IS psdsf-rdm; mechanism choice "
                     "lives in engine.solve",
        "backend": _IS_NUMPY,
        "placement": dict(forward=True),
        "fill": dict(forward=True),
        "round": "n/a — the numpy sweep is Gauss-Seidel by construction; "
                 "engine.solve rejects round!='gauss' before dispatch",
        "layout": dict(forward=True),
        "precision": _F64,
        "accel": dict(forward=True),
    },
    ("src/repro/core/psdsf.py", "solve_psdsf_tdm"): {
        "mechanism": "n/a — this function IS psdsf-tdm; mechanism choice "
                     "lives in engine.solve",
        "backend": _IS_NUMPY,
        "placement": dict(forward=True),
        "fill": dict(forward=True),
        "round": "n/a — the numpy sweep is Gauss-Seidel by construction; "
                 "engine.solve rejects round!='gauss' before dispatch",
        "layout": dict(forward=True),
        "precision": _F64,
        "accel": dict(forward=True),
    },
    ("src/repro/core/baselines.py", "solve_level_fill"): {
        "mechanism": "n/a — takes the prebuilt level-rate matrix; the "
                     "mechanism name is validated by level_rate_matrix",
        "backend": _IS_NUMPY,
        "placement": dict(forward=True),
        "fill": dict(forward=True),
        "round": "n/a — numpy sweep, Gauss-Seidel by construction",
        "layout": dict(forward=True),
        "precision": _F64,
        "accel": dict(forward=True),
    },
    ("src/repro/core/baselines.py", "solve_cdrfh"): {
        "mechanism": "n/a — this function IS cdrfh (re-validated by "
                     "level_rate_matrix inside _solve_baseline)",
        "backend": _IS_NUMPY,
        "placement": dict(via="kwargs", forward=True),
        "fill": dict(via="kwargs", forward=True),
        "round": "n/a — numpy sweep, Gauss-Seidel by construction",
        "layout": dict(via="kwargs", forward=True),
        "precision": _F64,
        "accel": dict(via="kwargs", forward=True),
    },
    ("src/repro/core/baselines.py", "solve_tsf"): {
        "mechanism": "n/a — this function IS tsf (re-validated by "
                     "level_rate_matrix inside _solve_baseline)",
        "backend": _IS_NUMPY,
        "placement": dict(via="kwargs", forward=True),
        "fill": dict(via="kwargs", forward=True),
        "round": "n/a — numpy sweep, Gauss-Seidel by construction",
        "layout": dict(via="kwargs", forward=True),
        "precision": _F64,
        "accel": dict(via="kwargs", forward=True),
    },
    ("src/repro/core/baselines.py", "solve_cdrf"): {
        "mechanism": "n/a — this function IS cdrf (re-validated by "
                     "level_rate_matrix inside _solve_baseline)",
        "backend": _IS_NUMPY,
        "placement": dict(via="kwargs", forward=True),
        "fill": dict(via="kwargs", forward=True),
        "round": "n/a — numpy sweep, Gauss-Seidel by construction",
        "layout": dict(via="kwargs", forward=True),
        "precision": _F64,
        "accel": dict(via="kwargs", forward=True),
    },
    ("src/repro/core/psdsf_jax.py", "psdsf_solve_jax"): {
        "mechanism": dict(param="mode", forward=True),
        "backend": _IS_JAX,
        "placement": dict(forward=True),
        "fill": dict(forward=True),
        "round": dict(forward=True),
        "layout": _FULL_LAYOUT,
        "precision": "n/a — dtype follows the input arrays (_solve_dtype); "
                     "there is no precision knob on the batch solves",
        "accel": dict(forward=True),
    },
    ("src/repro/core/psdsf_jax.py", "psdsf_solve_batched"): {
        "mechanism": dict(param="mode", forward=True),
        "backend": _IS_JAX,
        "placement": dict(forward=True),
        "fill": dict(forward=True),
        "round": dict(forward=True),
        "layout": _FULL_LAYOUT,
        "precision": "n/a — dtype follows the input arrays (_solve_dtype)",
        "accel": dict(forward=True),
    },
    ("src/repro/core/psdsf_jax.py", "psdsf_resolve_batched"): {
        "mechanism": dict(param="mode", forward=True),
        "backend": _IS_JAX,
        "placement": dict(forward=True),
        "fill": dict(forward=True),
        "round": dict(forward=True),
        "layout": _FULL_LAYOUT,
        "precision": "n/a — dtype follows the input arrays (_solve_dtype)",
        "accel": dict(forward=True),
    },
    ("src/repro/core/baselines_jax.py", "baseline_solve_jax"): {
        "mechanism": "n/a — takes the prebuilt level-rate matrix; build it "
                     "with level_rate_matrix(_jnp), which validates",
        "backend": _IS_JAX,
        "placement": dict(forward=True),
        "fill": dict(forward=True),
        "round": dict(forward=True),
        "layout": _FULL_LAYOUT,
        "precision": "n/a — dtype follows the input arrays (_solve_dtype)",
        "accel": dict(forward=True),
    },
    ("src/repro/core/baselines_jax.py", "baseline_solve_batched"): {
        "mechanism": "n/a — takes the prebuilt level-rate matrix; build it "
                     "with level_rate_matrix(_jnp), which validates",
        "backend": _IS_JAX,
        "placement": dict(forward=True),
        "fill": dict(forward=True),
        "round": dict(forward=True),
        "layout": _FULL_LAYOUT,
        "precision": "n/a — dtype follows the input arrays (_solve_dtype)",
        "accel": dict(forward=True),
    },
    ("src/repro/core/baselines_jax.py", "solve_baseline_jax"): {
        "mechanism": dict(forward=True),
        "backend": _IS_JAX,
        "placement": dict(forward=True),
        "fill": dict(forward=True),
        "round": dict(forward=True),
        "layout": dict(forward=True),
        "precision": "n/a — dtype follows the input arrays (_solve_dtype)",
        "accel": dict(forward=True),
    },
    ("src/repro/core/dynamic.py", "DistributedPSDSF.__init__"): {
        "mechanism": dict(param="mode", forward=False),
        "backend": dict(param="engine", forward=False),
        "placement": dict(forward=True),
        "fill": dict(forward=False),
        "round": "n/a — a tick is a single asynchronous sweep visit; there "
                 "is no outer iteration to choose",
        "layout": dict(forward=True),
        "precision": dict(forward=False),
        "accel": dict(forward=False),
    },
    ("src/repro/sched/serving.py", "DynamicDispatcher.__init__"): {
        "mechanism": dict(param="mode", forward=True),
        "backend": dict(param="engine", forward=True),
        "placement": dict(forward=True),
        "fill": dict(forward=True),
        "round": "n/a — delegates to DistributedPSDSF, whose tick has no "
                 "outer iteration",
        "layout": dict(forward=True),
        "precision": dict(forward=True),
        "accel": dict(forward=True),
    },
    ("src/repro/sched/churn.py", "ChurnSimulator.__init__"): {
        "mechanism": dict(forward=False),
        "backend": "n/a — the churn tick always runs the jitted engine",
        "placement": dict(forward=False),
        "fill": dict(forward=False),
        "round": dict(forward=False),
        "layout": dict(forward=True),
        "precision": "n/a — the tick engine runs float32 buffers by design "
                     "(10^3-user churn scale)",
        "accel": dict(forward=False),
    },
    ("src/repro/sched/cluster.py", "schedule"): {
        "mechanism": dict(forward=True),
        "backend": "n/a — numpy allocator registry only; the jitted paths "
                   "are engine.solve's job",
        "placement": dict(forward=True, sinks=_ALLOCATOR_SINKS),
        "fill": dict(via="kwargs", forward=True, sinks=_ALLOCATOR_SINKS),
        "round": "n/a — numpy layer; sweep allocators reject a round kwarg "
                 "with a TypeError, closed-form ones validate it",
        "layout": dict(via="kwargs", forward=True, sinks=_ALLOCATOR_SINKS),
        "precision": _F64,
        "accel": dict(via="kwargs", forward=True, sinks=_ALLOCATOR_SINKS),
    },
    ("src/repro/sched/cluster.py", "schedule_detail"): {
        "mechanism": dict(forward=True),
        "backend": "n/a — numpy allocator registry only",
        "placement": dict(forward=True, sinks=_ALLOCATOR_SINKS),
        "fill": dict(via="kwargs", forward=True, sinks=_ALLOCATOR_SINKS),
        "round": "n/a — numpy layer; sweep allocators reject a round kwarg "
                 "with a TypeError, closed-form ones validate it",
        "layout": dict(via="kwargs", forward=True, sinks=_ALLOCATOR_SINKS),
        "precision": _F64,
        "accel": dict(via="kwargs", forward=True, sinks=_ALLOCATOR_SINKS),
    },
    ("src/repro/sched/serving.py", "admitted_rates"): {
        "mechanism": dict(forward=True),
        "backend": "n/a — numpy allocator registry only",
        "placement": dict(forward=True, sinks=_ALLOCATOR_SINKS),
        "fill": dict(via="kwargs", forward=True, sinks=_ALLOCATOR_SINKS),
        "round": "n/a — numpy layer; sweep allocators reject a round kwarg "
                 "with a TypeError, closed-form ones validate it",
        "layout": dict(via="kwargs", forward=True, sinks=_ALLOCATOR_SINKS),
        "precision": _F64,
        "accel": dict(via="kwargs", forward=True, sinks=_ALLOCATOR_SINKS),
    },
}

#: modules whose jitted ``static_argnames`` are swept for axis names nobody
#: declared (AX108): a new engine axis almost always lands here first.
STATIC_ARGNAME_MODULES = (
    "src/repro/core/psdsf_jax.py",
    "src/repro/core/baselines_jax.py",
    "src/repro/core/dynamic.py",
    "src/repro/sched/churn.py",
)

#: static argnames that are NOT engine axes (sweep knobs and axis aliases;
#: aliases map onto AXES via the per-entry ``param=`` specs above)
STATIC_NON_AXES = frozenset({"mode", "engine", "round_mode", "max_rounds",
                             "mechanism"}) | frozenset(AXES)


# ---------------------------------------------------------------------------
# jit-purity

JIT_PURITY = dict(
    #: directories scanned for traced roots and their call closure
    scan_dirs=("src/repro/core", "src/repro/sched"),
    #: name patterns that are traced code even without a jit decorator
    #: (anchored tightly: ``_repack_if_routed`` is a numpy host method)
    root_patterns=(r"^_solve_core", r"^_fill_one_server",
                   r"^_repack_core$", r"^_repack_refill_core$",
                   r"^_routed_fill_core$", r"^stranded_fraction_jnp$"),
    #: trace-time gates: host-side validation helpers that run during
    #: tracing on static (non-traced) arguments; excluded from the closure
    trace_time_gates=frozenset({
        "_check_placement", "_check_accel",
        "_reject_lexmm_traced", "get_placement", "min"}),
    #: numpy attributes that are trace-safe constants/dtypes, not ops
    np_const_allow=frozenset({
        "inf", "nan", "pi", "e", "newaxis", "float32", "float64", "int32",
        "int64", "bool_", "ndarray", "dtype", "finfo", "iinfo", "errstate"}),
)


# ---------------------------------------------------------------------------
# kernel triples

KERNELS = dict(
    dir="src/repro/kernels",
    triple=("kernel.py", "ops.py", "ref.py"),
    #: per-package test file that must import the package; unlisted
    #: packages default to the CI interpret lane's file
    default_test="tests/test_kernels_interpret.py",
    tests={
        "flash_attention": "tests/test_kernel_flash_attention.py",
        "ssd_scan": "tests/test_kernel_ssd_and_decode.py",
        "decode_attention": "tests/test_kernel_ssd_and_decode.py",
    },
)


# ---------------------------------------------------------------------------
# observability coverage

OBSERVABILITY = {
    "SolveInfo": dict(
        module="src/repro/core/placement.py",
        writer_groups={
            "numpy": ("src/repro/core/placement.py",
                      "src/repro/core/psdsf.py",
                      "src/repro/core/baselines.py",
                      "src/repro/core/extensions.py"),
            "jax": ("src/repro/core/engine.py",
                    "src/repro/core/baselines_jax.py"),
        },
        waivers={
            ("lp_calls", "jax"): "lexmm LP certificates always solve "
                                 "host-side; the jax lexmm path is the "
                                 "identity on the level solve",
            ("lp_iters", "jax"): "lexmm LP certificates always solve "
                                 "host-side (see lp_calls)",
            ("warm_hits", "jax"): "router warm-start reuse exists only in "
                                  "the host RouterState",
            ("warm_fallbacks", "jax"): "router warm-start reuse exists "
                                       "only in the host RouterState",
            ("solve_ms", "jax"): "router wall-clock telemetry; the jitted "
                                 "solves are timed by the benchmarks layer",
            ("stage_ms", "jax"): "per-stage router timings exist only in "
                                 "the host RouterState",
            ("router_mode", "jax"): "router mode labels host RouterState "
                                    "solves only",
            ("servers_skipped", "jax"): "active-set skipping is the numpy "
                                        "bucketed sweep's optimization; "
                                        "the jitted sweep always visits "
                                        "every server",
        },
    ),
    "ChurnRecord": dict(
        module="src/repro/sched/churn.py",
        writer_groups={
            "tick": ("src/repro/sched/churn.py",),
        },
        waivers={},
    ),
}


# ---------------------------------------------------------------------------
# docstring coverage (ported from benchmarks/lint_docstrings.py)

DOCSTRINGS = dict(
    packages=("src/repro/core", "src/repro/sched"),
    min_percent=95.0,
)

#: default committed baseline location
BASELINE_PATH = "benchmarks/analysis_baseline.json"
