import os
import sys

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke
# tests and benches must see 1 device (the 512-device placeholder mesh
# belongs exclusively to repro.launch.dryrun).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_root, "src"))
sys.path.insert(0, _root)  # for `import benchmarks` in integration tests
