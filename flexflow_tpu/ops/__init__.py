"""Operator library: every compute op of the reference's src/ops/ inventory
(SURVEY §2.2) as a jax-traceable Op subclass, registered by OperatorType."""
from .base import Op, OpContext, op_class_for, register_op  # noqa: F401
from . import linear  # noqa: F401
from . import conv  # noqa: F401
from . import elementwise  # noqa: F401
from . import normalization  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import attention  # noqa: F401
from . import latent_attention  # noqa: F401
from . import embedding  # noqa: F401
from . import moe_ops  # noqa: F401
from . import noop  # noqa: F401
from . import recurrent  # noqa: F401
from . import ssm  # noqa: F401
from . import gated_delta  # noqa: F401
from . import fused  # noqa: F401
