from repro.kernels.expert_ffn.kernel import row_tile  # noqa: F401
from repro.kernels.expert_ffn.ops import (dispatch_plan,  # noqa: F401
                                          expert_ffn)
from repro.kernels.expert_ffn.ref import expert_ffn_ref  # noqa: F401
