import os
import sys

# tests must see ONE device (the dry-run sets 512 for itself in-process)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
