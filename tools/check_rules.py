"""The repo's lint rule set, loaded by ``python -m repro.check --codebase``.

Rules live here — next to the conventions they enforce — rather than inside
the package, so tightening an allowlist is a reviewable one-line diff. Each
entry is a `repro.check.lint.LintRule`; the ``exempt`` patterns are
repo-relative globs.

Conventions enforced (codes in ``repro.check.diagnostics.CODES``):

RPL100  words are the model currency. Only the byte-model modules — the
        traffic/byte models under ``repro.plan``, all of ``repro.sim`` /
        ``repro.roofline``, and the checker itself — may multiply a count by
        a dtype width. Everyone else consumes ``TrafficReport.bytes`` /
        ``Tensor.nbytes`` / ``Schedule.vmem_bytes``.
RPL101  per-access energy constants are defined once, in
        ``src/repro/roofline/constants.py``.
RPL102  never assign a ``*_words`` name from a ``*_bytes`` name (or vice
        versa) without an explicit conversion. Applies everywhere, tests
        included-by-omission (tests corrupt units on purpose and are not
        linted).
RPL103  ``pl.pallas_call`` is invoked in exactly one place —
        ``repro.kernels.launch.run`` — so every kernel launch is a
        `LaunchPlan` the RPC04x dataflow analyzer can trace and certify.
        Only ``src/repro/kernels/`` may touch it.
RPL104  raw wall-clock reads (``time.perf_counter`` and friends) live only
        in ``repro.obs`` (the tracing primitives), ``benchmarks/`` (the
        harnesses), and ``launch/planserve.py`` (the virtual-clock load
        generator). Everywhere else measures via ``repro.obs.Stopwatch`` so
        every timed interval can double as a trace span.
RPL105  no bare ``except:``, and no ``except Exception: pass``, anywhere
        under ``src/repro/``: the fault-injection layer (``repro.faults``,
        ``repro.errors``) exists so failures are dispatched on by *type* —
        a swallowed exception is an un-observable fault. Harness/script
        roots (``benchmarks/``, ``examples/``, ``tools/``) are exempt.
"""

from repro.check.lint import (adhoc_timing_rule, bare_except_rule,
                              cross_assign_rule, magic_energy_rule,
                              raw_byte_arith_rule, raw_pallas_rule)

#: modules allowed to convert words -> bytes
BYTE_MODEL_MODULES = (
    "src/repro/plan/conv_model.py",    # conv byte model
    "src/repro/plan/gemm_model.py",    # VMEM working sets + GEMM byte model
    "src/repro/plan/graph.py",         # Tensor.nbytes
    "src/repro/plan/netplan.py",       # residency-adjusted bus reports
    "src/repro/plan/schedule.py",      # Schedule.vmem_bytes
    "src/repro/plan/workload.py",      # workload footprint helpers
    "src/repro/sim/*",                 # the simulator prices bytes
    "src/repro/roofline/*",            # roofline is a bytes/s model
    "src/repro/check/*",               # the verifier recomputes conversions
    "src/repro/obs/export.py",         # GB/s counter track derivation
)

RULES = [
    raw_byte_arith_rule(BYTE_MODEL_MODULES),
    magic_energy_rule(("src/repro/roofline/constants.py",)),
    cross_assign_rule(),
    raw_pallas_rule(("src/repro/kernels/*",)),
    adhoc_timing_rule(("src/repro/obs/*", "benchmarks/*",
                       "src/repro/launch/planserve.py")),
    bare_except_rule(("benchmarks/*", "examples/*", "tools/*")),
]
