"""Test bootstrap: put src/ on sys.path (tests run with or without
PYTHONPATH=src), make the tests dir importable (the hypothesis fallback
shim lives here), and keep jax on the default single CPU device — a test
that needs more devices forces them in a subprocess."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))
