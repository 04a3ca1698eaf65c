"""Tests of the benchmark's own files; they run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def tiny_config():
    with open(os.path.join(DATA, "tiny-olmo2.json")) as f:
        return json.load(f)
