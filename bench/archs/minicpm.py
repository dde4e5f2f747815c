"""``model_type`` minicpm: the dense SiLU-gated decoder of ``_dense``."""

from bench.archs._dense import *  # noqa: F401,F403
