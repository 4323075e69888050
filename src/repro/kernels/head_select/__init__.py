from repro.kernels.head_select.kernel import (BLOCK_C,  # noqa: F401
                                              NEG_INF, head_row_tile)
from repro.kernels.head_select.ops import head_select  # noqa: F401
from repro.kernels.head_select.ref import (head_select_ref,  # noqa: F401
                                           head_select_stats_ref,
                                           merge_head_stats)
