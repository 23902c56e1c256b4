"""The declared contract surface ``tracecheck`` enforces.

This module is *policy, not mechanism*: it names the jitted entry points of
the twin, the parameters that are static at those boundaries, and the files
allowed to do things that are forbidden elsewhere.  The mechanism lives in
:mod:`tools.lint.engine` / :mod:`tools.lint.rules`.

Keeping the registry in one reviewed file is the point — adding a new jit
entry point, a new bf16 site or a new nondeterminism allowance is a visible
one-line diff here, not an invisible drift in the codebase.
"""

from __future__ import annotations

#: Jitted entry points of the twin: dotted module path -> static parameter
#: names at that boundary.  Everything *reachable* from these functions runs
#: under ``jax.jit`` tracing, so TC002 (no concretization) and TC003 (no
#: Python control flow on traced values) apply to their parameters.
#:
#: The engine additionally auto-registers every module-level function that
#: is jitted in place — ``@functools.partial(jax.jit, static_argnames=...)``
#: decorators and ``name = jax.jit(fn, static_argnames=...)`` assignments —
#: deriving the static set from the decorator/call itself.  List a function
#: here only when it is jitted indirectly (``twin_step`` via
#: ``twin_step_jit``) or its statics cannot be derived syntactically.
JIT_ENTRYPOINTS: dict[str, tuple[str, ...]] = {
    # the pure twin cycle — jitted as state.twin_step_jit (donating) and by
    # callers via jax.jit(twin_step); cfg rides in the pytree as aux data
    "repro.core.state.twin_step": (),
    # the batched scenario engine body behind _run_scenarios_jit[_donated];
    # statics mirror scenarios._RUN_STATICS (also auto-derived, kept here so
    # the contract survives a rename of the module-level alias)
    "repro.core.scenarios._run_scenarios_body": (
        "max_hosts", "t_bins", "max_starts_per_bin", "model",
        "use_pallas", "precision"),
    # the fused per-tile readout shared by the Pallas kernel and its XLA
    # reference — everything after the bare ``*`` is compile-time
    "repro.kernels.des_readout._tile_readout": (
        "model", "precision", "dt_seconds", "tb_t"),
    # fleet twinning: scan(vmap(twin_step)) behind twin._run_fleet_jit
    "repro.core.twin._run_fleet": (),
    # lane-masked fleet step behind twin._fleet_step_masked_jit — the ONE
    # compiled program the streaming service (repro.serve) multiplexes
    # every tenant mix onto
    "repro.core.twin._fleet_step_masked": (),
    # the traced calibration grid (fleet argmin + optional per-host refit,
    # calibrate._per_host_refit) — jitted indirectly inside twin_step and
    # directly by differential tests as jax.jit(..., static_argnames="spec")
    "repro.core.calibrate.calibrate_traced": ("spec",),
    # NOTE: the D-axis sharded fleet programs (twin._run_fleet_sharded_jit /
    # twin._fleet_step_masked_sharded_jit, static over "mesh") are
    # decorator-form module-level jits and auto-register; they wrap the two
    # _run_fleet/_fleet_step_masked bodies listed above via shard_map.
}

#: Parameter names that are static *by repo convention* wherever they appear
#: in jit-reachable code (frozen config pytree aux data, model/backend
#: selectors, compile-time tile sizes).  TC002/TC003 trust this naming
#: discipline — a traced value must not be bound to one of these names.
STATIC_PARAM_NAMES: frozenset[str] = frozenset({
    "self", "cls", "cfg", "config", "spec", "mesh", "model", "backend",
    "interpret", "precision", "mode", "dtype", "axis", "name", "kind",
    "max_hosts", "max_backfill", "max_starts_per_bin", "t_bins",
    "tb_t", "tb_c", "dt_seconds", "num_hosts", "history_windows",
    "chunk", "use_pallas", "donate", "shard",
    # SLO spec tuples: static structure (thresholds/comparisons picked at
    # trace time), only the observation stream is traced
    "slos",
})

#: Module-level donating jits (dotted path -> donated positional indices)
#: that TC004 tracks *in addition to* the ``jax.jit(..., donate_argnums=…)``
#: assignments it discovers syntactically.  Discovery covers everything in
#: this repo today; the explicit seeds keep the contract stable if a
#: donating jit is ever constructed through a helper the scanner cannot see.
DONATING_JITS: dict[str, tuple[int, ...]] = {
    "repro.core.state.twin_step_jit": (0,),
    "repro.core.twin._run_fleet_jit": (0,),
    "repro.core.twin._fleet_step_masked_jit": (0,),
    "repro.core.scenarios._run_scenarios_jit_donated": (0,),
}

#: Files allowed to cast to bfloat16 (TC005).  The precision policy
#: (PR 7, golden-pinned by tests/golden/readout_bf16.npz): bf16 is legal
#: exactly on the derived performance leaves (tflops/efficiency) inside the
#: fused readout — sustainability math stays f32 everywhere.
BF16_ALLOWED_FILES: frozenset[str] = frozenset({
    "src/repro/kernels/des_readout.py",
})

#: Heavy/non-vendored packages that must never be imported bare (TC006):
#: ROADMAP "optional-dependency policy" — try-import with stdlib fallback,
#: or ``pytest.importorskip`` in tests.  CI runs without them installed.
OPTIONAL_MODULES: tuple[str, ...] = ("zstandard", "hypothesis")

#: Directories (repo-relative prefixes) where TC007 forbids ambient
#: nondeterminism: the deterministic heart of the twin.  ``runtime/`` is
#: included because it produces the traced failure schedules that what-if
#: results (and their goldens) depend on.  ``serve/`` is the
#: streaming service loop: time is injected (Clock), producers are seeded —
#: an ambient clock there would break replay determinism silently.
DETERMINISTIC_DIRS: tuple[str, ...] = (
    "src/repro/core/", "src/repro/kernels/", "src/repro/runtime/",
    "src/repro/serve/")

#: (file, source) pairs TC007 tolerates — the I/O-shell allow-list.
#: Empty today: the orchestrator's wall-clock pacing goes through its
#: injectable Clock (references, not calls, so TC007 stays quiet), and
#: platform-dispatch sites carry inline suppressions with reasons.  Add a
#: pair here only when a whole file/source combination is intended.
NONDETERMINISM_ALLOWED: frozenset[tuple[str, str]] = frozenset()

#: Directories TC001 (no jit construction in function/loop bodies) scans.
#: tests/ is exempt by design: a per-test jit dies with the process, and
#: tests deliberately build throwaway jits to probe retrace behavior.
JIT_HYGIENE_DIRS: tuple[str, ...] = ("src/", "benchmarks/")

#: hypothesis example budget above which a test must be marked ``slow``
#: (pytest.ini runs tier 1 with ``-m "not slow"``; see ROADMAP test tiers).
MAX_FAST_EXAMPLES: int = 50
