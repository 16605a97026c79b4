"""The benchmark's own tests run on the CPU: ``pytest chipbench/tests``."""
import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
