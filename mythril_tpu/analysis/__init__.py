"""Analysis driver: symbolic wrapper, detection modules, reporting.

Reference layout counterpart: ``mythril/analysis/`` (⚠unv) —
``symbolic.py`` (SymExecWrapper), ``security.py`` (fire_lasers),
``module/`` (DetectionModule + loader + the SWC suite), ``report.py``.
"""

from .report import Issue, Report, SWC_TITLES
from .symbolic import (AnalysisContext, BatchBuild, SymExecWrapper,
                       build_batch)
from .security import fire_lasers
from .module.base import DetectionModule, EntryPoint
from .module.loader import ModuleLoader, register_module
from .module import modules  # noqa: F401  (registers the SWC suite)

__all__ = [
    "Issue", "Report", "SWC_TITLES",
    "AnalysisContext", "BatchBuild", "SymExecWrapper", "build_batch",
    "fire_lasers",
    "DetectionModule", "EntryPoint", "ModuleLoader", "register_module",
]
