import os
import sys

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the dry-run sets 512 itself). Tests that
# need a multi-device mesh spawn a subprocess with XLA_FLAGS set.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def pytest_configure(config):
    # Tier-1 CI runs `-m "not slow"`; the nightly job runs everything.
    config.addinivalue_line(
        "markers",
        "slow: heavyweight model/train/system tests, run in the nightly "
        "full-suite CI job (tier-1 deselects them with -m 'not slow')")
