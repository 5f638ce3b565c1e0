from repro.kernels.safeguard_filter.kernel import (  # noqa: F401
    sqdist_from_tile_grams)
from repro.kernels.safeguard_filter.ops import (  # noqa: F401
    fused_accumulate_sqdist, pairwise_sqdist)
from repro.kernels.safeguard_filter import ref                  # noqa: F401
