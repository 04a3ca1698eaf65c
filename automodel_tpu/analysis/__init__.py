"""Static-analysis layer: parallelism auditor + repo invariant linter.

Two pillars (see ``docs/guides/static_analysis.md``):

* :mod:`automodel_tpu.analysis.jaxpr_audit` — walk a jitted step's
  ClosedJaxpr / compiled HLO and produce a structured collective census,
  sharding audit and host-transfer scan.  Golden censuses for the dryrun
  flagship legs are checked in under ``tests/data/golden_census/`` and
  asserted by tier-1 (``tests/unit_tests/test_analysis.py``).
* :mod:`automodel_tpu.analysis.lint` — AST-based repo invariant linter
  (rules L002-L007), zero third-party deps; run by ``tools/lint.py`` and
  the tier-1 ``tests/unit_tests/test_lint_clean.py``.
"""

from automodel_tpu.analysis.jaxpr_audit import (  # noqa: F401
    CollectiveCensus,
    audit_param_shardings,
    census_of,
    compile_cache_size,
    jaxpr_census,
)
from automodel_tpu.analysis.lint import Finding, lint_paths  # noqa: F401
