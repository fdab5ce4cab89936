"""Pure-jax op implementations — the kernel corpus.

This package is the TPU analog of the reference's `src/operator/` (225k LoC of
C++/CUDA kernels): every function here is a *pure* function of jax arrays,
lowered by XLA onto the MXU/VPU, fused automatically. The NDArray/np frontends
wrap these through `apply_op` for eager+taped execution; Gluon layers call
them directly inside traced forwards.

Layout convention: NCHW/NCW/NCDHW ("channels first"), matching the reference's
default conv/pool layout so model code ports unchanged. XLA transposes
internally to its preferred layout at negligible cost on TPU.
"""
from . import nn  # noqa: F401
from . import tensor  # noqa: F401
from . import linalg  # noqa: F401
from . import vision  # noqa: F401
from . import legacy  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import pallas_attention  # noqa: F401
from . import pallas_qk_prep  # noqa: F401
from . import pallas_kda  # noqa: F401
from . import pallas_mla_heads  # noqa: F401
from . import short_conv  # noqa: F401
from .registry import list_ops, register_op, get_op  # noqa: F401
