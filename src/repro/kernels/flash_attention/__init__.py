from repro.kernels.flash_attention.ops import (               # noqa: F401
    flash_attention_train, train_kernel_fits)
from repro.kernels.flash_attention import ref                  # noqa: F401
