"""repro.analysis -- static analysis gate for every compiled tick program.

Walks closed jaxprs and lowered HLO of the shipped programs (4 backends
x frozen/learning x telemetry on/off, plus the serve chunk and refill
programs) and lints every Pallas kernel's launch descriptor --
all without executing a tick.  See DESIGN.md §14 for the rule catalogue
and ``python -m repro.analysis.check --help`` for the CLI.
"""

from repro.analysis.findings import ERROR, INFO, WARNING, Finding, Report

__all__ = ["Finding", "Report", "ERROR", "WARNING", "INFO"]
