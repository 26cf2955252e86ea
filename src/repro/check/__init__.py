"""`repro.check` — static plan/kernel verifier + unit-discipline lint.

Three layers, one diagnostic currency (`Diagnostic`, stable ``RPC``/``RPL``
codes):

  * **IR verifier** (`check`, `verify`): proves Schedules satisfy eq (1) and
    the block/extent/VMEM budgets, Plans' recorded traffic matches the
    analytical model word-for-word, NetworkGraph edges conserve words and
    carry consistent dtypes, NetPlans' residency sets fit their byte budget
    over live intervals, and Pallas launches (`check_network_kernels`) have
    well-formed BlockSpec geometry — all before anything runs or compiles.
  * **Kernel-body dataflow analyzer** (`repro.check.dataflow`, RPC04x): an
    abstract interpreter over the Pallas kernel bodies proving race-freedom,
    scratch initialization, output coverage, eq (3)-shaped accumulation
    chains, and — per candidate, vectorized over whole search spaces — that
    the words the kernels actually move equal the analytical model.
  * **Codebase lint** (`check_codebase`, rules in ``tools/check_rules.py``):
    AST rules keeping words-vs-bytes conversions, energy constants, raw
    ``pallas_call`` escapes, and deprecated shims where they belong.

CLI: ``python -m repro.check [--plans] [--codebase] [--dataflow]
[--github]``.
Inline: ``plan.plan(..., checked=True)``, ``plan.plan_graph(...,
checked=True)``, ``sim.simulate(..., checked=True)``; `run_network_kernels`
always pre-flights its launches.
"""

from repro.check.api import check_codebase, check_plans, verify
from repro.check.dataflow import (DataflowReport, LaunchAnalysis,
                                  SpaceCertificate, analyze_launch,
                                  certify_conv_space, certify_matmul_space,
                                  check_dataflow, check_network_dataflow,
                                  conv_dataflow, flash_dataflow,
                                  matmul_dataflow, preflight_flash_dataflow)
from repro.check.diagnostics import (CODES, CheckError, CodeInfo, Diagnostic,
                                     Severity, code_table, errors,
                                     raise_on_error, render_all)
from repro.check.footprint import (KernelTrace, UntraceableKernel,
                                   trace_launch, visit_structure)
from repro.check.kernels import (check_conv_launch, check_flash_launch,
                                 check_launch, check_matmul_launch,
                                 check_network_kernels,
                                 preflight_flash_launch,
                                 preflight_network_kernels)
from repro.check.lint import (LintRule, default_rules, lint_file, lint_repo,
                              load_rules)
from repro.check.passes import (check, check_graph, check_netplan, check_plan,
                                check_schedule, check_traffic, check_workload,
                                summarize)

__all__ = [
    "Diagnostic", "Severity", "CodeInfo", "CODES", "CheckError",
    "errors", "raise_on_error", "render_all", "code_table",
    "check", "verify", "summarize",
    "check_workload", "check_schedule", "check_traffic", "check_plan",
    "check_graph", "check_netplan",
    "check_launch", "check_conv_launch",
    "check_matmul_launch", "check_flash_launch",
    "check_network_kernels",
    "preflight_network_kernels", "preflight_flash_launch",
    "LintRule", "default_rules", "load_rules", "lint_file", "lint_repo",
    "check_plans", "check_codebase",
    "DataflowReport", "LaunchAnalysis", "SpaceCertificate",
    "analyze_launch", "conv_dataflow", "matmul_dataflow", "flash_dataflow",
    "certify_conv_space", "certify_matmul_space", "check_dataflow",
    "check_network_dataflow", "preflight_flash_dataflow",
    "KernelTrace", "UntraceableKernel", "trace_launch", "visit_structure",
]
