from repro.kernels.wfa.ops import (  # noqa: F401
    wfa_align, wfa_align_np, wfa_align_trace)
from repro.kernels.wfa.ref import ref_scores  # noqa: F401
