import os
import sys

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device.  Multi-device tests spawn subprocesses with
# their own XLA_FLAGS.

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
