"""The benchmark's own tests: run by hand from the repo's root,
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`` (not tier-1)."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
