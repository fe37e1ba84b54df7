from .aggregate import (  # noqa: F401
    AggSpec,
    global_aggregate,
    grouped_aggregate_direct,
    grouped_aggregate_sorted,
)
from .filter import compact, filter_page, filter_project_page  # noqa: F401
from .hashing import hash_rows  # noqa: F401
from .join import BuildSide, build_sorted, join_expand, join_n1  # noqa: F401
from .sort import (  # noqa: F401
    SortKey,
    apply_permutation,
    distinct_page,
    limit_page,
    sort_page,
    top_n,
)
