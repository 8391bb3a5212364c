"""These tests rehearse the benchmark without a chip. They are not part
of the repo's tier-1 suite (``tests/``); run them with
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
