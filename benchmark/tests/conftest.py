"""Tests of the benchmark itself. Run on the CPU, outside tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
