"""Pallas edge-relaxation substrate for the graph engine.

Select it engine-wide with ``repro.core.operators.set_substrate("pallas")``
(or per call via the ``substrate=`` argument on push/pull/advance/relax).
"""

from .ops import advance_frontier, edge_relax, intersect_count  # noqa: F401
from .ref import (  # noqa: F401
    KINDS,
    advance_ref,
    batched_push_ref,
    batched_relax_ref,
    det_push_ref,
    det_relax_ref,
    det_scatter_add,
    intersect_ref,
    neutral_for,
    pull_ref,
    push_ref,
    relax_ref,
    scatter_reduce,
    sorted_lower_bound,
    sorted_segment_reduce,
)
