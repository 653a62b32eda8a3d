"""Operator library.

Each module defines Op subclasses (see core/op.py) covering the reference's
src/ops/ inventory (SURVEY.md §2.3), lowered to jax/XLA instead of
cuDNN/cuBLAS kernels.
"""
from . import core_ops  # noqa: F401
from . import linear  # noqa: F401
from . import conv  # noqa: F401
from . import elementwise  # noqa: F401
from . import norm  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import embedding  # noqa: F401
from . import attention  # noqa: F401
from . import latent_attention  # noqa: F401
from . import moe  # noqa: F401
from . import rnn  # noqa: F401
from . import ssm  # noqa: F401
