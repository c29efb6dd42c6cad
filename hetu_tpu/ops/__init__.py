from .attention import sdpa, sdpa_reference
from .paged_attention import paged_attention_reference
from .ragged_paged_attention import (ragged_paged_attention,
                                     ragged_paged_attention_reference)
from .functional import *  # noqa: F401,F403
# NB: importing the .attention submodule binds `ops.attention` to the module;
# rebind the op function explicitly (it must win).
from .functional import attention  # noqa: F401
