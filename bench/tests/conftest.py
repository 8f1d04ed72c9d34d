"""The benchmark's own tests (not tier-1: the repository's ``pytest.ini``
collects ``tests/`` only). Run them from the checkout's root:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Four virtual CPU devices stand in for the four-chip cell, and the compile
cache goes to a temporary directory."""
import os
import sys
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench_jax_cache_"))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))
