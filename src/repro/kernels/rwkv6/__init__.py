from .kernel import wkv_kernel  # noqa: F401
from .ops import wkv_op  # noqa: F401
from .ref import wkv_ref  # noqa: F401
