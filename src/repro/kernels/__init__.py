# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# The "pallas" Aligner engine (kernels/engine.py) routes the pipeline's
# hot paths through these kernels; kernels/config.py resolves whether
# they run compiled (TPU/GPU) or interpreted (CPU).

from .config import (  # noqa: F401
    COMPILED_BACKENDS,
    default_interpret,
    device_summary,
    enable_compile_cache,
    resolve_interpret,
)
