"""Operator library: importing this package registers all ops."""
from . import registry
from . import math        # noqa: F401
from . import tensor      # noqa: F401
from . import nn          # noqa: F401
from . import random_ops  # noqa: F401
from . import init_ops    # noqa: F401
from . import contrib     # noqa: F401
from . import vision      # noqa: F401
from . import extra       # noqa: F401
from . import pallas_kernels  # noqa: F401
from . import linear_attention  # noqa: F401
from . import attention   # noqa: F401
from . import moe         # noqa: F401
from . import short_conv  # noqa: F401
from . import quantization as quantization_ops  # noqa: F401
from . import control_flow  # noqa: F401
from .registry import get, exists, list_ops, register, Op  # noqa: F401
