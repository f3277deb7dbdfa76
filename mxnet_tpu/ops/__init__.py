"""Operator library: registry + op families.

The TPU-native replacement for ``src/operator/**`` (SURVEY.md N7): each op is
one pure jittable JAX function registered in :mod:`.registry`; gradients come
from jax.vjp, shape/type inference from jax.eval_shape.
"""
from .registry import (Operator, register, get_op, list_ops, apply_op, param,
                       OPS)

# importing the families populates the registry
from . import elemwise      # noqa: F401
from . import reduce        # noqa: F401
from . import matrix        # noqa: F401
from . import nn            # noqa: F401
from . import delta_rule    # noqa: F401  (Kimi Delta Attention)
from . import rnn           # noqa: F401
from . import init_random   # noqa: F401
from . import optimizer_ops # noqa: F401
from . import shape_hints   # noqa: F401  (installs arg names + infer hints)
from . import vision_fork   # noqa: F401  (yangyu12 fork custom vision ops)
from . import contrib_det   # noqa: F401  (SSD/RCNN detection contrib ops)
from . import contrib_misc  # noqa: F401  (CTC/FFT/resize/… contrib ops)
from . import linalg        # noqa: F401  (_linalg_* BLAS3/LAPACK family)
from . import spatial       # noqa: F401  (STN/correlation/SVM ops)
from . import control_flow  # noqa: F401  (_foreach scan op)
from . import quantization  # noqa: F401  (INT8 quantize/quantized_* ops)
from . import image_ops     # noqa: F401  (_image_* transform ops)
from . import misc_parity   # noqa: F401  (histogram/ravel/scatter/… tails)
