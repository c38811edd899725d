"""Query executor: plan compiler + call dispatch (reference executor.go)."""

from .executor import (  # noqa: F401
    TOPN_EXTRAS, WRITE_CALLS, ExecutionError, Executor, topn_extras,
)
from .plan import PlanError  # noqa: F401
from .results import (  # noqa: F401
    FieldRow, GroupCount, Pair, RowIdentifiers, RowResult, ValCount,
)
