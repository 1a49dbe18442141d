import pathlib
import sys

import jax

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

# tests compile fresh every time: no persistent compilation cache (the
# entry points' repro.kernels.enable_compile_cache respects this)
jax.config.update("jax_enable_compilation_cache", False)
