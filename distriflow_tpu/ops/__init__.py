"""Pallas TPU kernels for the hot ops.

The reference delegates all numerics to tfjs WebGL kernels (SURVEY.md §2.1);
the equivalent "native op layer" here is Pallas — hand-scheduled TPU kernels
for the ops XLA's default fusion leaves on the table:

- :func:`flash_attention` — fused online-softmax attention (never
  materializes the [S, S] score matrix in HBM);
- :func:`fused_softmax_cross_entropy` — per-row logsumexp CE over the vocab
  dim without materializing softmax probabilities;
- :func:`depthwise3x3_groupnorm` — depthwise-3x3 + GroupNorm + ReLU6 in one
  VMEM-resident sweep (MobileNet's two measured hot spots fused).

On a TPU backend every kernel is compiled by Mosaic. Off-TPU (the CPU test
suite) the same kernel bodies run in the Pallas interpreter — a correctness
aid that says nothing about the compiled kernel. :func:`default_interpret`
is the single switch; ``chip_smoke.py`` asserts that the programs it runs on
the chip contain the Mosaic custom calls, so nothing on that path may depend
on the switch being ``True``.
"""

from distriflow_tpu.ops.depthwise_gn import (  # noqa: F401
    depthwise3x3_groupnorm,
    depthwise_gn_supported,
)
from distriflow_tpu.ops.flash_attention import flash_attention  # noqa: F401
from distriflow_tpu.ops.fused_ce import (  # noqa: F401
    fused_softmax_cross_entropy,
    fused_softmax_cross_entropy_per_example,
    fused_sparse_softmax_cross_entropy,
    fused_sparse_softmax_cross_entropy_per_example,
)


def default_interpret() -> bool:
    """Pallas TPU kernels need a real TPU; interpret everywhere else."""
    import jax

    return jax.default_backend() != "tpu"


def default_use_flash() -> bool:
    """Single source of truth for flash-kernel auto-enablement (the
    compiled kernels exist only on TPU; interpret mode is test-only)."""
    return not default_interpret()
