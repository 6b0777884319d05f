import os
import sys

# the suite runs on the CPU with kernels in interpret mode; it and the child
# processes its tests start never claim an accelerator on the host
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# tests see ONE CPU device (the dry-run alone forces 512 placeholder devices)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
